"""Run one cell: find its pieces by name, set it up, time its window,
check what the window produced against the plain reference, read the
metrics and print the result line.

Nothing here names a configuration, a mix or a metric: a later cell
brings its own files (see ``bench/__init__.py``) and is found by the
names in ``BENCHMARK.json``.
"""
from __future__ import annotations

import argparse
import dataclasses
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
#: JAX's persistent compilation cache: ``$JAX_COMPILATION_CACHE_DIR``
#: where it is set, else a fixed directory inside the checkout (listed
#: in .gitignore), so only a cell's first run there compiles.
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


class BenchError(Exception):
    """The cell cannot be measured here; no result line is printed."""


# ---------------------------------------------------------------------------
# Finding the pieces by name
# ---------------------------------------------------------------------------
def load_json(path: str):
    with open(path) as fh:
        return json.load(fh)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell_entry(bm: dict, name: str) -> dict:
    for cell in bm["workloads"]:
        if cell["name"] == name:
            return cell
    raise BenchError(f"no workload {name!r} in BENCHMARK.json")


def _path(bench_dir: str, kind: str, name: str, ext: str) -> str:
    path = os.path.join(bench_dir, kind, name + ext)
    if not os.path.isfile(path):
        raise BenchError(f"no {kind} file {path}")
    return path


def load_config(name: str, bench_dir: str = BENCH_DIR) -> dict:
    return load_json(_path(bench_dir, "configs", name, ".json"))


def load_traffic(name: str, bench_dir: str = BENCH_DIR) -> dict:
    return load_json(_path(bench_dir, "traffic", name, ".json"))


def load_limits(cell: str, bench_dir: str = BENCH_DIR) -> dict:
    return load_json(_path(bench_dir, "limits", cell, ".json"))


def load_module(kind: str, name: str, bench_dir: str = BENCH_DIR):
    """Import ``<bench_dir>/<kind>/<name>.py`` (a driver, metric or
    reference) as a module of its own."""
    path = _path(bench_dir, kind, name, ".py")
    mod_name = f"_bench_{kind}_{name}".replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(mod_name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def peak_row(device_kind: str, bench_dir: str = BENCH_DIR) -> dict:
    peaks = load_json(os.path.join(bench_dir, "peaks.json"))
    if device_kind not in peaks:
        raise BenchError(f"device kind {device_kind!r} is not in peaks.json")
    return peaks[device_kind]


def metric_entries(bm: dict, cell: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics (``trace`` off) or per-layer ones
    (``trace`` on): those that list the cell, or list no cells."""
    group = bm["per_layer" if trace else "end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


# ---------------------------------------------------------------------------
# What the metric readers see
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Run:
    """One run's record; ``bench/metrics/<name>.py`` reads it."""
    cell: str
    config: dict
    traffic: dict
    peaks: dict                 # peaks.json row of the device kind
    n_devices: int
    setup_s: float
    compile_s: float            # trace + lower + compile during set-up
    calls: list                 # (dispatched, returned, ready) per call,
                                # host seconds (time.perf_counter)
    point_updates_per_call: int
    bytes_per_call: int         # algorithmic HBM bytes, all chips
    trace: object = None        # bench.trace.Summary with --trace 1


class CompileLog:
    """Durations of JAX's tracing, lowering and compiling events
    (``jax.monitoring``) while it is open."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.events: list[tuple[str, float]] = []

    def _listen(self, event, duration, **_):
        if event in self.EVENTS:
            self.events.append((event, duration))

    def __enter__(self):
        import jax.monitoring
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        import jax.monitoring
        jax.monitoring.unregister_event_duration_listener(self._listen)

    def seconds(self, since: int = 0) -> float:
        return sum(d for _, d in self.events[since:])

    def compiles(self, since: int = 0) -> int:
        return sum(e == self.EVENTS[2] for e, _ in self.events[since:])


# ---------------------------------------------------------------------------
# One run
# ---------------------------------------------------------------------------
def _enable_compile_cache() -> None:
    import jax
    path = os.environ.get(CACHE_ENV, "").strip() or CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _import_program() -> None:
    """The program under test comes from the checkout's ``src/``; a
    directory that holds only the benchmark cannot be measured."""
    try:
        import repro.core  # noqa: F401
    except ImportError as e:
        raise BenchError(f"the program is not in this checkout: {e}") from e


def _devices(chips: int, require_chip: bool):
    import jax
    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise BenchError(f"no TPU: JAX found {devices[0].platform!r}")
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips, JAX found "
                         f"{len(devices)}")
    return devices[:chips]


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks)


def _plan_counters() -> dict:
    from repro.core import plan as _plan
    stats = _plan.PLAN_CACHE.stats()
    return {k: stats[k] for k in ("lowers", "autotune_calls")}


def run_cell(cell: str, seed: int, seconds: float, trace: bool, *,
             bm: dict | None = None, bench_dir: str = BENCH_DIR,
             require_chip: bool = True, dtype: str | None = None,
             t_start: float | None = None) -> dict:
    """Run ``cell`` once and return its result line as a dict (the
    ``checks`` key last).  ``require_chip=False`` drops the look for a
    TPU (the CPU rehearsal); ``dtype`` runs the program at another
    storage precision (the control)."""
    t_start = time.perf_counter() if t_start is None else t_start
    bm = benchmark() if bm is None else bm
    entry = cell_entry(bm, cell)
    config = load_config(entry["config"], bench_dir)
    traffic = load_traffic(entry["traffic"], bench_dir)
    limits = load_limits(cell, bench_dir)
    driver_mod = load_module("drivers", traffic["driver"], bench_dir)
    reference = load_module("references", config["reference"], bench_dir)
    readers = {m["name"]: load_module("metrics", m["name"], bench_dir)
               for m in metric_entries(bm, cell, trace)}

    _import_program()
    import jax
    devices = _devices(int(entry["chips"]), require_chip)
    kind = devices[0].device_kind
    peaks = peak_row(kind, bench_dir) if require_chip else {}

    with CompileLog() as compiles:
        driver = driver_mod.Driver(config, traffic, seed, devices,
                                   reference=reference, dtype=dtype)
        with jax.profiler.TraceAnnotation("bench.setup"):
            driver.setup()
        setup_s = time.perf_counter() - t_start
        compile_s = compiles.seconds()
        n_events = len(compiles.events)
        plans_before = _plan_counters()

        log_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
        try:
            if trace:
                options = jax.profiler.ProfileOptions()
                options.python_tracer_level = 0     # harness spans only
                jax.profiler.start_trace(log_dir, profiler_options=options)
            with jax.profiler.TraceAnnotation("bench.window"):
                calls = driver.window(seconds=seconds)
            if trace:
                jax.profiler.stop_trace()
            plans_after = _plan_counters()
            in_window = {k: plans_after[k] - plans_before[k]
                         for k in plans_before}
            in_window["compiles"] = compiles.compiles(n_events)
            print("# in the window: plan-cache lowers "
                  f"{in_window['lowers']}, autotunes "
                  f"{in_window['autotune_calls']}, XLA compiles "
                  f"{in_window['compiles']} (all should be 0)", flush=True)
            memory_peak = _memory_peak(devices)
            summary = None
            if trace:
                from bench import trace as _trace
                summary = _trace.load(_trace.find_xplane(log_dir))
        finally:
            if trace:
                shutil.rmtree(log_dir, ignore_errors=True)
        readings = driver.check()

    run = Run(cell=cell, config=config, traffic=traffic, peaks=peaks,
              n_devices=len(devices), setup_s=setup_s, compile_s=compile_s,
              calls=calls,
              point_updates_per_call=driver.point_updates_per_call,
              bytes_per_call=driver.bytes_per_call, trace=summary)
    metrics = {}
    for m in metric_entries(bm, cell, trace):
        value = readers[m["name"]].read(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}

    checks = {}
    for name, value in readings.items():
        if name not in limits:
            raise BenchError(f"reading {name!r} has no limit in limits/"
                             f"{cell}.json")
        checks[name] = {"value": value, "limit": limits[name]}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices), "memory_peak_bytes": memory_peak}
    result = {"correct": correct, "attempted": len(calls), "failed": 0,
              "metrics": metrics, "device": device}
    if summary is not None:
        busy = [summary.busy_ns(d) for d in summary.devices]
        device["busy_s"] = sum(busy) / len(busy) / 1e9
        device["window_s"] = summary.window_ns / 1e9
        idlest = min(summary.devices, key=summary.busy_ns)
        result["breakdown"] = {"device_ops": summary.top_ops(),
                               "idle_gaps": summary.top_gaps(idlest)}
    result["checks"] = checks
    return result


def main(argv=None, t_start: float | None = None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dtype", default=None,
                    help="run the program at this storage dtype (the "
                         "lower-precision control)")
    args = ap.parse_args(argv)
    # libtpu logs to /tmp/tpu_logs unless told otherwise.
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    _enable_compile_cache()
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), dtype=args.dtype,
                          t_start=t_start)
    except BenchError as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0

"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV and writes full validation detail to
benchmarks/results/paper_validation.json.
"""
from __future__ import annotations

import json
import os
import sys

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)                       # the benchmarks package
sys.path.insert(0, os.path.join(_ROOT, "src"))  # repro

from benchmarks.paper_figs import (bench4_schema_errors,  # noqa: E402
                                   fig01_roofline, fig10_speedup,
                                   fig11_energy, fig12_gpu, fig13_pims,
                                   fig14_mapping, stencil_wallclock,
                                   structure_bench, table4_instructions,
                                   temporal_blocking)
from benchmarks.lm_roofline import lm_roofline  # noqa: E402
from benchmarks.pipelines import (bench6_schema_errors,  # noqa: E402
                                  pipelines_bench)
from benchmarks.serving import (bench5_schema_errors,  # noqa: E402
                                serving_bench)
from benchmarks.serving_load import (bench8_schema_errors,  # noqa: E402
                                     serving_load_bench)
from benchmarks.slabs import (bench7_schema_errors,  # noqa: E402
                              slabs_bench)
from benchmarks.stencil_cluster import stencil_cluster_mapping  # noqa: E402
from repro.configs import env as _env  # noqa: E402

BENCHES = (
    fig01_roofline, fig10_speedup, fig11_energy, fig12_gpu, fig13_pims,
    fig14_mapping, table4_instructions, temporal_blocking,
    structure_bench, stencil_wallclock, serving_bench, pipelines_bench,
    slabs_bench, serving_load_bench, lm_roofline, stencil_cluster_mapping,
)


def _write_bench(detail: dict, key: str, schema_errors, filename: str,
                 root: str) -> str:
    payload = detail[key]
    errs = schema_errors(payload)
    if errs:
        raise SystemExit(f"{filename} schema invalid: {errs}")
    path = os.path.join(root, filename)
    with open(path, "w") as f:
        json.dump(payload, f, indent=1, default=float)
        f.write("\n")
    return path


def write_bench4(detail: dict, root: str = _ROOT) -> str:
    """Write the structure bench's BENCH_4.json at the repo root (the
    perf-trajectory artifact future PRs diff against); schema-checked
    before writing."""
    return _write_bench(detail, "bench4", bench4_schema_errors,
                        "BENCH_4.json", root)


def write_bench5(detail: dict, root: str = _ROOT) -> str:
    """Write the serving bench's BENCH_5.json at the repo root
    (batched-vs-sequential throughput + plan-cache stats);
    schema-checked before writing."""
    return _write_bench(detail, "bench5", bench5_schema_errors,
                        "BENCH_5.json", root)


def write_bench6(detail: dict, root: str = _ROOT) -> str:
    """Write the pipelines bench's BENCH_6.json at the repo root
    (fused-vs-staged modeled HBM bytes + measured wallclock per
    workload); schema-checked before writing."""
    return _write_bench(detail, "bench6", bench6_schema_errors,
                        "BENCH_6.json", root)


def write_bench7(detail: dict, root: str = _ROOT) -> str:
    """Write the slab-streaming bench's BENCH_7.json at the repo root
    (slabbed-vs-whole-grid wallclock + modeled host<->device traffic,
    forced-budget bit-identity); schema-checked before writing."""
    return _write_bench(detail, "bench7", bench7_schema_errors,
                        "BENCH_7.json", root)


def write_bench8(detail: dict, root: str = _ROOT) -> str:
    """Write the continuous-batching load bench's BENCH_8.json at the
    repo root (open-loop Poisson sweep: sustained throughput vs the
    sequential/one-shot baselines, per-point latency percentiles, f64
    bit-identity leg); schema-checked before writing."""
    return _write_bench(detail, "bench8", bench8_schema_errors,
                        "BENCH_8.json", root)


def main() -> None:
    # JAX picks the platform (JAX_PLATFORMS pins one); the compile cache
    # goes through the one config helper.
    _env.enable_compile_cache()
    out_dir = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                           "results")
    os.makedirs(out_dir, exist_ok=True)
    all_detail = {}
    print("name,us_per_call,derived")
    for bench in BENCHES:
        rows, detail = bench()
        all_detail[bench.__name__] = detail
        for name, us, derived in rows:
            print(f"{name},{us:.3f},{derived}")
    with open(os.path.join(out_dir, "paper_validation.json"), "w") as f:
        json.dump(all_detail, f, indent=1, default=float)
    print(f"# wrote {write_bench4(all_detail['structure_bench'])}",
          file=sys.stderr)
    print(f"# wrote {write_bench5(all_detail['serving_bench'])}",
          file=sys.stderr)
    print(f"# wrote {write_bench6(all_detail['pipelines_bench'])}",
          file=sys.stderr)
    print(f"# wrote {write_bench7(all_detail['slabs_bench'])}",
          file=sys.stderr)
    print(f"# wrote {write_bench8(all_detail['serving_load_bench'])}",
          file=sys.stderr)
    summaries = {k: v.get("summary") for k, v in all_detail.items()
                 if isinstance(v, dict) and v.get("summary")}
    print("# --- summaries ---", file=sys.stderr)
    for k, v in summaries.items():
        print(f"# {k}: {json.dumps(v, default=float)}", file=sys.stderr)


if __name__ == "__main__":
    main()

"""The mesh cell rehearsed on the CPU, and the counts and readers that
only the mesh cell has.

The rehearsal runs ``heat3d-2k-mesh.solve1000``'s configuration at a
tiny grid (6 steps a call: one fused block of 4 and the 2-step
remainder) through ``harness.run_cell`` on four virtual CPU devices, in
a child process so that this one keeps its single-device view.  A sound
run comes out correct; the control (the program at bfloat16 storage)
and each fault come out not correct:

* a step that returns its state unchanged;
* an answer altered where it is produced;
* the exchange between chips left out: each shard pads its own block
  with zeros where its neighbours' faces belong.

No number timed here means anything."""
import json
import os
import shutil
import subprocess
import sys
import tempfile
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, mesh_work  # noqa: E402
from bench import trace as _trace  # noqa: E402

REAL = "heat3d-2k-mesh.solve1000"
TINY = "tinymesh.k6"
TINY_GRID = [32, 32, 256]           # 16x16x256 a shard
CONTROL_DTYPE = "bfloat16"
CASES = ("sound", "control", "state_unchanged", "answer_altered",
         "exchange_left_out")


def make_tiny_bench(tmp_dir: str):
    """A copy of ``bench/`` with the tiny mesh cell added, and the
    BENCHMARK.json dict that names it."""
    bench_dir = os.path.join(tmp_dir, "bench")
    shutil.copytree(harness.BENCH_DIR, bench_dir)
    bm = harness.benchmark()
    real = harness.cell_entry(bm, REAL)
    config = dict(harness.load_config(real["config"]), grid=TINY_GRID)
    with open(os.path.join(bench_dir, "configs", "tinymesh.json"), "w") as fh:
        json.dump(config, fh)
    with open(os.path.join(bench_dir, "traffic", "k6-mesh.json"), "w") as fh:
        json.dump(dict(harness.load_traffic(real["traffic"]),
                       steps_per_call=6), fh)
    shutil.copy(os.path.join(bench_dir, "limits", REAL + ".json"),
                os.path.join(bench_dir, "limits", TINY + ".json"))
    bm["workloads"].append({"name": TINY, "config": "tinymesh",
                            "traffic": "k6-mesh", "chips": 4})
    return bench_dir, bm


def _fault(case):
    """``(module, attribute, replacement)`` that puts ``case``'s fault
    into the program, or ``None``."""
    import jax.numpy as jnp
    from repro.core import halo
    if case == "state_unchanged":
        return halo, "execute_plan", lambda plan, grid: grid
    if case == "answer_altered":
        orig = halo.execute_plan

        def altered(plan, grid):
            out = orig(plan, grid)
            return out.at[(0,) * out.ndim].add(0.25)
        return halo, "execute_plan", altered
    if case == "exchange_left_out":
        def zero_faces(x, axis, depth, axis_name, **_):
            pad = [(0, 0)] * x.ndim
            pad[axis] = (depth, depth)
            return jnp.pad(x, pad)
        return halo, "exchange_halo_1axis", zero_faces
    return None


def rehearse(out_path: str) -> None:
    """Run every case on four virtual devices; write their results."""
    import jax
    results = {}
    with tempfile.TemporaryDirectory() as tmp:
        bench_dir, bm = make_tiny_bench(tmp)
        for case in CASES:
            fault = _fault(case)
            if fault:
                module, name, replacement = fault
                orig = getattr(module, name)
                setattr(module, name, replacement)
            jax.clear_caches()
            try:
                results[case] = harness.run_cell(
                    TINY, 2**33 + 5, 0.05, False, bm=bm, bench_dir=bench_dir,
                    require_chip=False,
                    dtype=CONTROL_DTYPE if case == "control" else None)
            finally:
                if fault:
                    setattr(module, name, orig)
    with open(out_path, "w") as fh:
        json.dump(results, fh)


@pytest.fixture(scope="module")
def rehearsal(tmp_path_factory):
    out = str(tmp_path_factory.mktemp("mesh") / "results.json")
    env = dict(os.environ, XLA_FLAGS=(
        "--xla_force_host_platform_device_count=4 "
        + os.environ.get("XLA_FLAGS", "")), JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, __file__, out], env=env,
                          capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-4000:]
    with open(out) as fh:
        return json.load(fh)


def test_sound_run_is_correct_on_four_devices(rehearsal):
    res = rehearsal["sound"]
    assert res["correct"], res["checks"]
    assert res["device"]["count"] == 4
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {"point_updates_per_s", "setup_s"}
    assert res["checks"]["max_abs_gap"]["value"] < 1e-5


def test_control_at_bfloat16_is_not_correct(rehearsal):
    res = rehearsal["control"]
    assert not res["correct"]
    gap = res["checks"]["max_abs_gap"]
    assert gap["value"] > 3 * gap["limit"]


@pytest.mark.parametrize("case", ["state_unchanged", "answer_altered",
                                  "exchange_left_out"])
def test_fault_in_the_timed_path_is_not_correct(rehearsal, case):
    res = rehearsal[case]
    assert not res["correct"], res["checks"]


# ---------------------------------------------------------------------------
# mesh_work
# ---------------------------------------------------------------------------
def _config(grid, mesh, grid_axes=("sx", "sy", None), sweeps=4):
    return {"stencil": "heat3d", "grid": list(grid), "dtype": "float32",
            "boundary": "zero", "sweeps": sweeps, "mesh": list(mesh),
            "mesh_axes": ["sx", "sy"], "grid_axes": list(grid_axes)}


def test_exchange_by_hand_at_2x2_with_deep_halo_4():
    """16x16x8 over 2x2, 8x8x8 a chip: each chip has one neighbour per
    axis, so it receives one 4x8x8 face on each axis and the 4x4x8
    corner between them."""
    config = _config((16, 16, 8), (2, 2))
    per_chip = 4 * 8 * 8 + 8 * 4 * 8 + 4 * 4 * 8
    assert mesh_work.exchange_points(config, 4) == 4 * per_chip
    assert mesh_work.exchange_bytes_per_call(
        config, {"steps_per_call": 8}) == 2 * 4 * per_chip * 4
    assert mesh_work.exchange_bytes_per_call(
        config, {"steps_per_call": 8}, "bfloat16") == 2 * 4 * per_chip * 2


def test_exchange_by_hand_at_a_shard_narrower_than_the_deep_halo():
    """8x4x4 over 4x1, 2x4x4 a chip, deep halo 4: the edge chips reach
    two neighbours (4 of the grid's 8 rows they do not hold, the
    far one a hop away), the middle ones every other row (6); the
    zero fill beyond the grid's edge is not counted."""
    config = _config((8, 4, 4), (4, 1))
    row = 4 * 4
    assert mesh_work.exchange_points(config, 4) == (4 + 6 + 6 + 4) * row
    # 6 steps at sweeps=4: one exchange 4 deep, one 2 deep (2 rows
    # each side, one edge chip's worth less at each end)
    two_deep = (2 + 4 + 4 + 2) * row
    assert mesh_work.exchange_points(config, 2) == two_deep
    assert mesh_work.exchange_bytes_per_call(
        config, {"steps_per_call": 6}) == ((4 + 6 + 6 + 4) * row
                                           + two_deep) * 4


def test_the_mesh_cell_exchanges_about_16_mib_a_chip_per_block():
    config = harness.load_config("heat3d-2k-mesh")
    per_chip = (1028 * 1028 - 1024 * 1024) * 512 * 4
    assert mesh_work.exchange_points(config, 4) * 4 == 4 * per_chip
    assert mesh_work.exchange_bytes_per_call(
        config, harness.load_traffic("solve1000-mesh")) == 250 * 4 * per_chip


def test_a_periodic_grid_is_refused():
    with pytest.raises(ValueError):
        mesh_work.exchange_points(dict(_config((16, 16, 8), (2, 2)),
                                       boundary="periodic"), 4)


# ---------------------------------------------------------------------------
# the Mesh-layer readers, on synthetic traces
# ---------------------------------------------------------------------------
def _op(start, end, kind):
    return _trace.Op(start, end, f"{kind}.{start}", kind)


def _run(devices, calls=2, config=None, traffic=None):
    summary = _trace.Summary(window=(0, 1000), devices=devices, spans=[])
    return types.SimpleNamespace(
        trace=summary, calls=[(0.0, 0.0, 0.0)] * calls,
        config=config or _config((16, 16, 8), (2, 2)),
        traffic=traffic or {"steps_per_call": 8})


def _reader(name):
    return harness.load_module("metrics", name)


def test_halo_exposed_share_counts_only_collectives_nothing_hides():
    """Chip 0: a collective 100-300 under a kernel 150-250 leaves 100 ns
    exposed; chip 1: a collective 500-800 under glue 500-600 and a
    kernel 700-900 leaves 100 ns, and one alone 900-1000 another 100."""
    devices = {
        0: [_op(100, 300, "collective"), _op(150, 250, "kernel")],
        1: [_op(500, 800, "collective"), _op(500, 600, "glue"),
            _op(700, 900, "kernel"), _op(900, 1000, "collective")],
    }
    assert _reader("halo_exposed_share").read(_run(devices)) == \
        pytest.approx(20.0)


def test_halo_exposed_share_is_zero_where_every_collective_overlaps():
    devices = {0: [_op(0, 500, "kernel"), _op(100, 200, "collective")]}
    assert _reader("halo_exposed_share").read(_run(devices)) == 0.0


def test_halo_exchange_gbps_is_exchanged_bytes_over_summed_collective_time():
    devices = {
        0: [_op(100, 300, "collective"), _op(150, 250, "kernel")],
        1: [_op(500, 800, "collective"), _op(900, 1000, "collective")],
    }
    run = _run(devices, calls=3)
    moved = 3 * mesh_work.exchange_bytes_per_call(run.config, run.traffic)
    assert _reader("halo_exchange_gbps").read(run) == pytest.approx(
        moved / 600e-9 / 1e9)


def test_mesh_readers_read_nothing_without_a_trace_or_a_collective():
    devices = {0: [_op(0, 500, "kernel")]}
    assert _reader("halo_exchange_gbps").read(_run(devices)) is None
    for name in ("halo_exposed_share", "halo_exchange_gbps",
                 "shard_kernel_roofline", "shard_glue_busy_share"):
        untraced = types.SimpleNamespace(
            trace=None, peaks={"hbm_bytes_per_s": 819e9})
        assert _reader(name).read(untraced) is None


def test_shard_readers_are_the_one_chip_readers():
    devices = {0: [_op(0, 400, "kernel"), _op(400, 500, "glue")],
               1: [_op(0, 300, "kernel"), _op(300, 500, "glue")]}
    run = _run(devices)
    run.peaks = {"hbm_bytes_per_s": 819e9}
    run.bytes_per_call = 10 ** 6
    assert _reader("shard_glue_busy_share").read(run) == \
        _reader("glue_busy_share").read(run) == pytest.approx(30.0)
    assert _reader("shard_kernel_roofline").read(run) == \
        _reader("fused_stencil_roofline").read(run)


if __name__ == "__main__":
    rehearse(sys.argv[1])

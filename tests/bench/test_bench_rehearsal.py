"""A benchmark run rehearsed on the CPU: the ``timestep`` driver drives
the program (Pallas in interpret mode) through ``harness.run_cell`` at
tiny sizes, with the look for a chip skipped and each cell's own limits.
Sound runs come out correct; the control (the program at bfloat16
storage) and each fault the cells can have come out not correct:

* a step that returns its state unchanged;
* an answer altered where it is produced.

(The cells have no batch and run on one chip, so "half of the batch
left out" and "the exchange between chips left out" have no
counterpart here.)  No number timed here means anything."""
import json
import os
import shutil
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness  # noqa: E402

#: tiny cell -> (the real cell whose config and limits it copies, and
#: its grid)
TINY = {
    "tiny2d.k6": ("jacobi2d-16k.solve1000", [64, 512]),
    "tiny3d.k6": ("heat3d-512.solve1000", [16, 16, 256]),
}
#: the control: the program at the storage precision one step below the
#: configurations' float32
CONTROL_DTYPE = "bfloat16"


def make_tiny_bench(tmp_dir: str):
    """A copy of ``bench/`` with the tiny cells added (6 steps a call:
    one fused block of 4 and the 2-step remainder), and the
    BENCHMARK.json dict that names them."""
    bench_dir = os.path.join(tmp_dir, "bench")
    shutil.copytree(harness.BENCH_DIR, bench_dir)
    bm = harness.benchmark()
    with open(os.path.join(bench_dir, "traffic", "k6.json"), "w") as fh:
        json.dump({"driver": "timestep", "steps_per_call": 6}, fh)
    for cell, (real, grid) in TINY.items():
        config = dict(harness.load_config(real.split(".")[0]), grid=grid)
        name = cell.split(".")[0]
        with open(os.path.join(bench_dir, "configs", name + ".json"),
                  "w") as fh:
            json.dump(config, fh)
        shutil.copy(os.path.join(bench_dir, "limits", real + ".json"),
                    os.path.join(bench_dir, "limits", cell + ".json"))
        bm["workloads"].append({"name": cell, "config": name,
                                "traffic": "k6", "chips": 1})
        for m in bm["end_to_end"] + bm["per_layer"]:
            if real in m.get("workloads", []):
                m["workloads"].append(cell)
    return bench_dir, bm


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    return make_tiny_bench(str(tmp_path_factory.mktemp("tiny")))


@pytest.fixture
def fresh_traces():
    """Retrace the program around a test that patches it."""
    import jax
    from repro.core import plan as _plan
    jax.clear_caches()
    _plan.runner.cache_clear()
    yield
    jax.clear_caches()
    _plan.runner.cache_clear()


def run(tiny, cell, **kw):
    bench_dir, bm = tiny
    return harness.run_cell(cell, kw.pop("seed", 2**31 + 7), 0.05, False,
                            bm=bm, bench_dir=bench_dir, require_chip=False,
                            **kw)


@pytest.mark.parametrize("cell", ["tiny2d.k6", "tiny3d.k6"])
def test_sound_run_is_correct(tiny, cell):
    res = run(tiny, cell)
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "checks"]
    assert res["correct"], res["checks"]
    assert res["attempted"] >= 1 and res["failed"] == 0
    assert set(res["metrics"]) == {
        m["name"] for m in harness.metric_entries(tiny[1], cell, False)}
    assert res["checks"]["max_abs_gap"]["value"] < 1e-5
    assert res["device"]["count"] == 1


@pytest.mark.parametrize("cell", ["tiny2d.k6", "tiny3d.k6"])
def test_control_at_bfloat16_is_not_correct(tiny, cell):
    res = run(tiny, cell, dtype=CONTROL_DTYPE)
    assert not res["correct"]
    gap = res["checks"]["max_abs_gap"]
    assert gap["value"] > 3 * gap["limit"]


def _unchanged(orig):
    return lambda plan, grid: grid


def _altered(orig):
    def execute(plan, grid):
        out = orig(plan, grid)
        return out.at[(0,) * out.ndim].add(0.25)
    return execute


@pytest.mark.parametrize("cell", ["tiny2d.k6", "tiny3d.k6"])
@pytest.mark.parametrize("fault", [_unchanged, _altered],
                         ids=["state_unchanged", "answer_altered"])
def test_fault_in_the_timed_path_is_not_correct(tiny, cell, fault,
                                                monkeypatch, fresh_traces):
    from repro.kernels import engine as keng
    monkeypatch.setattr(keng, "execute_plan", fault(keng.execute_plan))
    res = run(tiny, cell)
    assert not res["correct"], res["checks"]

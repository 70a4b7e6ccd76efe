"""The benchmark's contract and plumbing, on the CPU: ``BENCHMARK.json``
and the files it names, the algorithmic byte counts, finding a new
configuration, mix and metric by name, and refusing to measure where
there is no TPU or the device kind has no peaks."""
import json
import math
import os
import re
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import harness, work  # noqa: E402

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
WIDTH_WORDS = ("hidden", "intermediate", "latent", "state", "projection",
               "head", "expansion", "experts_per_tok")


@pytest.fixture(scope="module")
def bm():
    return harness.benchmark()


def test_benchmark_json_keys_and_names(bm):
    assert set(bm) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    assert bm["command"] == ["python3", "bench/run.py"]
    assert bm["paths"] == ["bench", "tests/bench"]
    assert 1 <= bm["run_seconds"] <= 51
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in bm[group]]
        assert len(names) == len(set(names))
        assert all(NAME.match(n) for n in names), names
    for c in bm["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith("bench/")
        assert not any(w in k for k in c["reduced"] for w in WIDTH_WORDS)
        assert not any(k.endswith(("_dim", "_rank")) for k in c["reduced"])
    for w in bm["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert sum(w["chips"] == 4 for w in bm["workloads"]) <= max(
        1, len(bm["workloads"]) // 2)
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) < 64 * 1024


def test_every_metric_has_a_reader_and_a_sound_entry(bm):
    e2e = {m["name"]: m for m in bm["end_to_end"]}
    assert "setup_s" in e2e and e2e["setup_s"]["bound"] <= 0.25
    for m in bm["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    for m in bm["per_layer"]:
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert m["moves"] in e2e
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        # the metric it moves is reported where it is
        for cell in m["workloads"]:
            assert cell in e2e[m["moves"]].get("workloads", [cell])
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert hasattr(harness.load_module("metrics", m["name"]), "read")


def test_every_cell_finds_its_files_and_reports_enough(bm):
    configs = {c["name"]: c for c in bm["configs"]}
    used = set()
    for w in bm["workloads"]:
        entry = configs[w["config"]]
        config = harness.load_config(w["config"])
        assert os.path.join(ROOT, entry["file"]) == os.path.join(
            harness.BENCH_DIR, "configs", w["config"] + ".json")
        assert config["reduced"] == entry["reduced"]
        assert set(entry["reduced"]) <= set(config)
        traffic = harness.load_traffic(w["traffic"])
        harness.load_module("drivers", traffic["driver"])
        harness.load_module("references", config["reference"])
        limits = harness.load_limits(w["name"])
        assert set(limits) == {"max_abs_gap"}
        e2e = [m["name"] for m in harness.metric_entries(bm, w["name"], False)]
        assert "setup_s" in e2e and len(e2e) >= 2
        assert harness.metric_entries(bm, w["name"], True)
        used.add(w["config"])
    assert used == set(configs)


def test_peaks_table_has_the_v5e_row():
    row = harness.peak_row("TPU v5 lite")
    assert row["hbm_bytes_per_s"] == 819e9
    assert row["hbm_bytes"] == 16e9
    assert row["bf16_flops_per_s"] == 197e12
    assert "cloud.google.com" in row["source"]
    with pytest.raises(harness.BenchError):
        harness.peak_row("TPU v0 imaginary")


@pytest.mark.parametrize("config, mix, blocks, grid_bytes", [
    ("jacobi2d-16k", "solve1000", 250, 16384 * 16384 * 4),
    ("heat3d-512", "solve1000", 250, 512 ** 3 * 4),
])
def test_algorithmic_bytes_per_call(config, mix, blocks, grid_bytes):
    cfg, traffic = harness.load_config(config), harness.load_traffic(mix)
    assert work.blocks_per_call(cfg, traffic) == blocks
    assert work.algorithmic_bytes_per_call(cfg, traffic) == (
        blocks * 2 * grid_bytes)
    assert work.algorithmic_bytes_per_call(cfg, traffic, "bfloat16") == (
        blocks * grid_bytes)
    assert work.point_updates_per_call(cfg, traffic) == (
        grid_bytes // 4 * traffic["steps_per_call"])


def test_remainder_block_counts_once():
    cfg = {"grid": [8, 128], "sweeps": 4, "dtype": "float32"}
    assert work.blocks_per_call(cfg, {"steps_per_call": 9}) == 3
    assert work.blocks_per_call(cfg, {"steps_per_call": 8}) == 2


def test_a_new_config_mix_and_metric_are_found_by_name(tmp_path, bm):
    bench_dir = tmp_path / "bench"
    for sub in ("configs", "traffic", "metrics", "limits"):
        (bench_dir / sub).mkdir(parents=True)
    (bench_dir / "configs" / "wave2d-1k.json").write_text(
        json.dumps({"stencil": "jacobi2d", "grid": [1024, 1024],
                    "sweeps": 2, "dtype": "float32"}))
    (bench_dir / "traffic" / "k7.json").write_text(
        json.dumps({"driver": "timestep", "steps_per_call": 7}))
    (bench_dir / "limits" / "wave2d-1k.k7.json").write_text(
        json.dumps({"max_abs_gap": 1e-3}))
    (bench_dir / "metrics" / "calls_done.py").write_text(
        "def read(run):\n    return len(run.calls)\n")
    cfg = harness.load_config("wave2d-1k", str(bench_dir))
    traffic = harness.load_traffic("k7", str(bench_dir))
    assert work.blocks_per_call(cfg, traffic) == 4
    assert harness.load_limits("wave2d-1k.k7", str(bench_dir)) == {
        "max_abs_gap": 1e-3}
    reader = harness.load_module("metrics", "calls_done", str(bench_dir))
    assert reader.read(harness.Run(
        "wave2d-1k.k7", cfg, traffic, {}, 1, 0.0, 0.0, [(0, 0, 1)] * 3,
        1, 1)) == 3
    grown = dict(bm, per_layer=bm["per_layer"] + [
        {"name": "calls_done", "unit": "calls", "better": "higher",
         "source": "host_clock", "layer": "Front",
         "moves": "point_updates_per_s", "workloads": ["wave2d-1k.k7"]}])
    assert [m["name"] for m in harness.metric_entries(
        grown, "wave2d-1k.k7", True)] == ["calls_done"]
    with pytest.raises(harness.BenchError):
        harness.load_config("absent", str(bench_dir))


def _numpy_step(name, u, c):
    """One step of the stencil, written from its definition in numpy."""
    import numpy as np
    p = np.pad(u, 1)
    inner = tuple(slice(1, -1) for _ in u.shape)
    faces = 0.0
    for ax in range(u.ndim):
        for lo in (slice(None, -2), slice(2, None)):
            faces = faces + p[tuple(lo if d == ax else inner[d]
                                    for d in range(u.ndim))]
    if name == "jacobi2d":
        return c * (u + faces)
    return u + c * (faces - 2 * u.ndim * u)


@pytest.mark.parametrize("name, shape, coefficient", [
    ("jacobi2d", (24, 40), 0.2), ("heat3d", (8, 12, 16), 0.1)])
def test_reference_steps_match_the_stencil_definition(name, shape,
                                                      coefficient):
    import numpy as np
    ref = harness.load_module("references", name)
    step = ref.make_step({"coefficient": coefficient})
    u = np.random.default_rng(0).random(shape, dtype=np.float32)
    got, want = u, u.astype(np.float64)
    for _ in range(3):
        got = step(got)
        want = _numpy_step(name, want, coefficient)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=1e-6)


def test_large_seeds_give_distinct_keys():
    import jax
    timestep = harness.load_module("drivers", "timestep")
    keys = {tuple(int(v) for v in jax.random.key_data(timestep.make_key(s)))
            for s in (5, 2**32 + 5, 2**33 + 5, 2**31 + 5)}
    assert len(keys) == 4


def test_grid_is_made_on_the_device_from_the_seed():
    import jax
    import numpy as np
    timestep = harness.load_module("drivers", "timestep")
    config = {"grid": [16, 256], "dtype": "float32"}
    traffic = {"steps_per_call": 4}
    sweeps = dict(config, sweeps=4)

    def grid(seed, dtype=None):
        return timestep.Driver(sweeps, traffic, seed, jax.devices(),
                               reference=None, dtype=dtype).make_grid()

    a, b = grid(2**33 + 1), grid(2**33 + 1)
    assert a.shape == (16, 256) and a.dtype == np.float32
    assert a.devices() == {jax.devices()[0]}
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert not np.array_equal(np.asarray(a), np.asarray(grid(2**33 + 2)))
    assert grid(5, "bfloat16").dtype == jax.numpy.bfloat16
    assert 0.0 <= float(a.min()) and float(a.max()) < 1.0


def _last_stdout_json(text: str):
    return [ln for ln in text.splitlines() if ln.startswith("{")]


def test_run_refuses_a_host_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "bench", "run.py"),
         "--workload", "jacobi2d-16k.solve1000", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert not _last_stdout_json(out.stdout)
    assert "no TPU" in out.stderr


def test_run_refuses_a_checkout_without_the_program(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(harness.BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    out = subprocess.run(
        [sys.executable, str(tmp_path / "bench" / "run.py"),
         "--workload", "jacobi2d-16k.solve1000", "--seed", "3",
         "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, env=dict(env, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert not _last_stdout_json(out.stdout)
    assert "program is not in this checkout" in out.stderr


def test_run_refuses_an_unknown_device_kind(monkeypatch, capsys):
    class FakeTpu:
        platform = "tpu"
        device_kind = "TPU v0 imaginary"

    monkeypatch.setattr(harness, "_enable_compile_cache", lambda: None)
    monkeypatch.setattr(harness, "_devices", lambda chips, require: [
        FakeTpu()] * chips)
    rc = harness.main(["--workload", "jacobi2d-16k.solve1000", "--seed", "3",
                       "--seconds", "1", "--trace", "0"])
    out = capsys.readouterr()
    assert rc != 0
    assert not _last_stdout_json(out.out)
    assert "TPU v0 imaginary" in out.err


def test_run_refuses_fewer_chips_than_the_cell_needs():
    with pytest.raises(harness.BenchError, match="needs 4 chips"):
        harness._devices(4, require_chip=False)

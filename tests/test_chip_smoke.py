"""``chip_smoke.py`` rehearsed on the CPU: its phase functions run at
tiny sizes in interpret mode (control flow and the reference comparison
only — no timing means anything here), the mesh phase runs on four
virtual host devices in a child process, and the script itself refuses
a host without a TPU."""
import json
import os
import subprocess
import sys
import textwrap

import pytest
import jax
import jax.numpy as jnp

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402


def test_default_phases_match_reference_in_interpret_mode():
    results = list(chip_smoke.default_phases(seed=0, interpret=True,
                                             scale=64, n_requests=8))
    names = [r["phase"] for r in results]
    assert names == [p[0] for p in chip_smoke.ENGINE_PHASES] + [
        "serving_loadgen_mix"]
    for res in results:
        assert res["ok"], res
        assert res["max_err"] <= res.get("tol", float("inf"))
    assert results[-1]["completed"] == results[-1]["requests"] == 8


def test_tolerance_grows_with_iters_and_norm():
    from repro.core import PAPER_PIPELINES, PAPER_STENCILS
    jac = PAPER_STENCILS["jacobi2d"]
    assert chip_smoke.f32_tolerance(jac, 8, 1.0) == pytest.approx(
        4 * 8 * 5 * 2.0 ** -23)
    assert (chip_smoke.f32_tolerance(jac, 16, 1.0)
            == 2 * chip_smoke.f32_tolerance(jac, 8, 1.0))
    rd = PAPER_PIPELINES["reaction_diffusion2d"]
    assert chip_smoke.f32_tolerance(rd, 8, 2.0) > 0


def test_mesh_phase_on_four_host_devices():
    code = textwrap.dedent(f"""
        import os, sys, json
        sys.path.insert(0, {ROOT!r})
        import chip_smoke
        res = chip_smoke.mesh_phase(shape=(16, 16, 256), iters=5,
                                    interpret=True)
        print(json.dumps(res))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["ok"] and res["shards"] == 4, res


def test_script_refuses_a_host_without_tpu(capsys):
    assert chip_smoke.main([]) != 0
    captured = capsys.readouterr()
    assert '"ok"' not in captured.out
    assert "no TPU" in captured.err


def test_compile_cache_lands_in_env_dir_or_repo(tmp_path, monkeypatch):
    """``enable_compile_cache`` keeps JAX's persistent cache where
    ``JAX_COMPILATION_CACHE_DIR`` says, and otherwise at the fixed
    ``<repo>/.jax_cache``."""
    from jax.experimental.compilation_cache import compilation_cache
    from repro.configs import env
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        monkeypatch.delenv(env.COMPILE_CACHE_ENV, raising=False)
        assert env.enable_compile_cache() == os.path.join(ROOT, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == env.REPO_CACHE_DIR
        monkeypatch.setenv(env.COMPILE_CACHE_ENV, str(tmp_path))
        assert env.enable_compile_cache() == str(tmp_path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
        compilation_cache.reset_cache()
        jax.jit(lambda x: x * 3.0 + 1.0)(jnp.arange(7.0)).block_until_ready()
        assert any(tmp_path.iterdir())
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()

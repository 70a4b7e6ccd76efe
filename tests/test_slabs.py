"""Out-of-core slab streaming (``ghost_strategy="stream-from-host"``).

A forced tiny ``CASPER_SLAB_BUDGET`` pushes grids that comfortably fit
in device memory onto the slab-streaming path, so the whole matrix runs
in tier-1 against the in-core whole-grid plan as the oracle:

* **f64 bit-identity** across rank {1,2,3} x boundary {zero, constant,
  periodic, reflect} x sweeps {1,3} x structure {star, separable} on the
  ref backend, plus a representative Pallas subset;
* edge cases: slab count 1, remainder iters (``iters = q*sweeps + r``,
  ``r > 0``), overlap deeper than a single slab (the multi-slab window
  gather), non-divisible outermost extents;
* the ``iters=0`` defensive-copy regression (run_plan must never alias
  the caller's buffer with a donated device buffer);
* serving: over-budget requests bypass the vmapped bucket path and are
  counted in ``ServeStats.n_slab_streamed``;
* plan-cache hygiene: the budget is part of the plan key, so plans
  lowered under different budgets never collide.
"""
import contextlib
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import enable_x64

import repro.core as rc
from repro.core import perfmodel as pm
from repro.core import plan as _plan
from repro.core.stencil import PAPER_PIPELINES, PAPER_STENCILS
from repro.kernels import stream as kstream

SHAPES = {1: (64,), 2: (24, 16), 3: (12, 8, 8)}

#: rank -> (star spec, separable spec); rank 1 has no separable
#: factorization, so both entries exercise distinct star radii instead.
SPECS = {
    1: (PAPER_STENCILS["jacobi1d"], PAPER_STENCILS["7pt1d"]),
    2: (PAPER_STENCILS["jacobi2d"], PAPER_STENCILS["blur2d"]),
    3: (PAPER_STENCILS["heat3d"], PAPER_STENCILS["star33_3d"]),
}

BOUNDARIES = ("zero", "constant(0.5)", "periodic", "reflect")


@contextlib.contextmanager
def forced_budget(n_bytes: int):
    """Scope ``CASPER_SLAB_BUDGET``: lowering *and* any remainder plan
    lowered mid-run consult it, so the whole run stays inside."""
    old = os.environ.get(pm.SLAB_BUDGET_ENV)
    os.environ[pm.SLAB_BUDGET_ENV] = str(int(n_bytes))
    try:
        yield
    finally:
        if old is None:
            os.environ.pop(pm.SLAB_BUDGET_ENV, None)
        else:
            os.environ[pm.SLAB_BUDGET_ENV] = old


def _host_grid(shape, seed=3):
    return np.random.default_rng(seed).standard_normal(shape)


def _check_streamed(spec, shape, sweeps, iters, backend, budget=None):
    """Lower whole-grid and forced-budget plans; assert the streamed
    result is f64 bit-identical to the whole-grid one and the streamed
    plan passes the static verifier."""
    from repro import analysis
    host = _host_grid(shape)
    with enable_x64():
        whole = _plan.lower(spec, shape, jnp.float64, backend=backend,
                            sweeps=sweeps)
        want = np.asarray(_plan.run_plan(whole, jnp.asarray(host), iters))
        if budget is None:
            budget = host.nbytes // 4
        with forced_budget(budget):
            slabbed = _plan.lower(spec, shape, jnp.float64,
                                  backend=backend, sweeps=sweeps)
            assert slabbed.streams_from_host, slabbed.ghost_strategy
            report = analysis.report_for(slabbed) or \
                analysis.verify_plan(slabbed)
            assert report.ok, report.pretty()
            got = np.asarray(_plan.run_plan(slabbed, host, iters))
    np.testing.assert_array_equal(got, want)
    return slabbed


# ---------------------------------------------------------------------------
# The matrix: rank x boundary x sweeps x structure, ref backend
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("ndim", (1, 2, 3))
@pytest.mark.parametrize("boundary", BOUNDARIES)
@pytest.mark.parametrize("sweeps", (1, 3))
@pytest.mark.parametrize("which", (0, 1), ids=("star", "separable"))
def test_slab_matrix_ref(ndim, boundary, sweeps, which):
    spec = SPECS[ndim][which].with_boundary(boundary)
    iters = 3 if sweeps == 1 else 7          # 7 = 2*3 + 1: remainder path
    _check_streamed(spec, SHAPES[ndim], sweeps, iters, "ref")


@pytest.mark.parametrize("boundary", ("zero", "periodic"))
@pytest.mark.parametrize("sweeps", (1, 3))
@pytest.mark.parametrize("which", (0, 1), ids=("star", "separable"))
def test_slab_matrix_pallas(boundary, sweeps, which):
    spec = SPECS[2][which].with_boundary(boundary)
    iters = 3 if sweeps == 1 else 7
    _check_streamed(spec, SHAPES[2], sweeps, iters, "pallas")


# ---------------------------------------------------------------------------
# Pipelines: fused chains stream; unfusable staged chains loop the slab
# executor per fused block (needs_host_streaming)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(PAPER_PIPELINES))
@pytest.mark.parametrize("backend", ("ref", "pallas"))
def test_slab_fused_pipeline(name, backend):
    pipe = PAPER_PIPELINES[name]
    assert pipe.fusable
    _check_streamed(pipe, (24, 16), sweeps=2, iters=5, backend=backend)


def test_slab_staged_pipeline():
    # mixed boundaries -> staged lowering; the budget still routes each
    # per-stage plan through the slab executor (needs_host_streaming)
    import dataclasses
    pipe = PAPER_PIPELINES["advect_diffuse2d"]
    stages = (pipe.stages[0],
              dataclasses.replace(pipe.stages[1], boundary="zero"))
    mixed = dataclasses.replace(pipe, name="mixed_ad2d", stages=stages)
    assert not mixed.fusable
    shape = (24, 16)
    host = _host_grid(shape)
    with enable_x64():
        g = jnp.asarray(host)
        want = g
        for _ in range(3):
            for s in mixed.stages:
                want = rc.apply_stencil(s, want)
        want = np.asarray(want)
        with forced_budget(host.nbytes // 4):
            plan = _plan.lower(mixed, shape, jnp.float64, backend="ref")
            assert not plan.fused
            assert not plan.streams_from_host      # staged: no slab cover
            assert plan.needs_host_streaming       # ...but streams anyway
            got = np.asarray(_plan.run_plan(plan, host, 3))
    np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Edge cases
# ---------------------------------------------------------------------------
def test_slab_count_one():
    # outermost extent 1: exactly one slab, whose residency may exceed
    # the budget (slab_len == 1 cannot shrink further; the verifier
    # exempts it)
    shape = (1, 64)
    spec = PAPER_STENCILS["jacobi2d"]
    plan = _check_streamed(spec, shape, sweeps=1, iters=2, backend="ref",
                           budget=8 * 64 // 2)
    assert plan.slabs == ((0, 1),)


def test_overlap_deeper_than_slab():
    # budget so tight every slab is a single row while the deep halo is
    # sweeps*halo = 3: each window gathers rows spanning several
    # neighboring slabs
    shape = (24, 16)
    spec = PAPER_STENCILS["jacobi2d"].with_boundary("periodic")
    plan = _check_streamed(spec, shape, sweeps=3, iters=7, backend="ref",
                           budget=1000)
    assert plan.slab_overlap == 3
    slab_len = plan.slabs[0][1] - plan.slabs[0][0]
    assert slab_len < plan.slab_overlap
    assert len(plan.slabs) == 24


def test_non_divisible_outermost():
    # budget sized for length-2 slabs over an odd extent: the trailing
    # slab is shorter and the cover still ends exactly at 23
    shape = (23, 16)
    plan = _check_streamed(PAPER_STENCILS["jacobi2d"], shape, sweeps=2,
                           iters=5, backend="ref", budget=2200)
    lengths = {stop - start for start, stop in plan.slabs}
    assert lengths == {1, 2}                 # a shorter trailing slab
    assert plan.slabs[-1][1] == 23


def test_remainder_iters_zero_remainder_equivalence():
    # iters divisible by sweeps and not: both must match the oracle
    spec = PAPER_STENCILS["jacobi1d"].with_boundary("reflect")
    for iters in (3, 4):
        _check_streamed(spec, (64,), sweeps=3, iters=iters, backend="ref")


# ---------------------------------------------------------------------------
# iters=0: defensive copy, never an alias (satellite 4 regression)
# ---------------------------------------------------------------------------
def test_iters_zero_returns_defensive_copy_numpy():
    spec = PAPER_STENCILS["jacobi2d"]
    host = _host_grid((24, 16))
    with enable_x64(), forced_budget(host.nbytes // 4):
        plan = _plan.lower(spec, host.shape, jnp.float64, backend="ref")
        out = _plan.run_plan(plan, host, 0)
    out_np = np.asarray(out)
    assert out_np is not host
    assert not np.shares_memory(out_np, host)
    np.testing.assert_array_equal(out_np, host)
    # mutating the copy must not leak back into the caller's buffer
    out_np[0, 0] += 1.0
    assert host[0, 0] != out_np[0, 0]


def test_iters_zero_returns_defensive_copy_jax():
    spec = PAPER_STENCILS["jacobi2d"]
    with enable_x64():
        g = jnp.asarray(_host_grid((24, 16)))
        plan = _plan.lower(spec, g.shape, jnp.float64, backend="ref")
        out = _plan.run_plan(plan, g, 0)
        assert out is not g
        np.testing.assert_array_equal(np.asarray(out), np.asarray(g))


# ---------------------------------------------------------------------------
# Serving: over-budget requests bypass bucketing with their own stat
# ---------------------------------------------------------------------------
def test_serve_slab_streamed_requests(monkeypatch):
    from repro.serve.stencil import StencilRequest, StencilServer
    big, small = (24, 16), (4, 4)
    big_bytes = 24 * 16 * 8
    monkeypatch.setenv(pm.SLAB_BUDGET_ENV, str(big_bytes // 4))
    with enable_x64():
        rng = np.random.default_rng(11)
        grids_big = [rng.standard_normal(big) for _ in range(3)]
        grids_small = [rng.standard_normal(small) for _ in range(2)]
        reqs = ([StencilRequest("jacobi2d", g, 2) for g in grids_big]
                + [StencilRequest("jacobi2d", g, 2) for g in grids_small])
        server = StencilServer(sweeps=1)
        results, stats = server.serve(reqs)
        spec = server.specs["jacobi2d"]
        for req, out in zip(reqs, results):
            want = jnp.asarray(req.grid)
            for _ in range(2):
                want = rc.apply_stencil(spec, want)
            np.testing.assert_array_equal(np.asarray(out),
                                          np.asarray(want))
    assert stats.n_slab_streamed == 3
    assert stats.n_requests == 5
    by_shape = {tuple(b["shape"]): b for b in stats.buckets}
    assert by_shape[big]["slab_streamed"] is True
    assert by_shape[small]["slab_streamed"] is False


# ---------------------------------------------------------------------------
# Plan-cache hygiene: the budget is part of the key
# ---------------------------------------------------------------------------
def test_budget_in_plan_key(monkeypatch):
    spec = PAPER_STENCILS["jacobi2d"]
    shape = (24, 16)
    with enable_x64():
        plain = _plan.lower(spec, shape, jnp.float64, backend="ref")
        assert not plain.streams_from_host
        with forced_budget(24 * 16 * 8 // 4):
            streamed = _plan.lower(spec, shape, jnp.float64, backend="ref")
            assert streamed.streams_from_host
            # a second lower under the same budget is a pure cache hit
            before = _plan.plan_cache_stats()
            again = _plan.lower(spec, shape, jnp.float64, backend="ref")
            delta = _plan.plan_cache_stats()
            assert again is streamed
            assert delta["lowers"] == before["lowers"]
        # and back outside the budget, the plain plan is served again
        back = _plan.lower(spec, shape, jnp.float64, backend="ref")
        assert back is plain


# ---------------------------------------------------------------------------
# Traffic model sanity (BENCH_7's analytic columns)
# ---------------------------------------------------------------------------
def test_host_device_traffic_model():
    spec = PAPER_STENCILS["jacobi2d"]
    shape = (24, 16)
    with enable_x64(), forced_budget(24 * 16 * 8 // 4):
        plan = _plan.lower(spec, shape, jnp.float64, backend="ref",
                           sweeps=2)
    t = kstream.host_device_traffic(plan, iters=5)
    assert t["n_slabs"] == len(plan.slabs)
    assert t["blocks"] == 3                   # 5 = 2*2 + 1 -> q+1 blocks
    assert t["whole_h2d_bytes"] == 24 * 16 * 8
    assert t["slab_h2d_bytes"] > t["whole_h2d_bytes"]
    assert t["overhead"] > 1.0


def test_streamed_plan_rejects_in_core_executor():
    # execute_plan in kernels.stream is streaming-only by contract
    spec = PAPER_STENCILS["jacobi2d"]
    with enable_x64():
        plan = _plan.lower(spec, (24, 16), jnp.float64, backend="ref")
    with pytest.raises(ValueError):
        kstream.execute_plan(plan, np.zeros((24, 16)))

"""``plan.run_plan``'s scan of fused blocks against chained blocks.

Pad-free Pallas plans run two fused blocks per scan step, so that XLA
need not copy the carried grid after every block; every other plan
runs one.  Either way ``run_plan`` over ``iters = q*sweeps + r`` is
bitwise ``q`` chained :func:`~repro.core.plan.execute` calls plus the
remainder block, for odd and even ``q``, in float64 and float32, for
one grid and for a vmapped serving bucket.
"""
import functools

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.analysis.jaxpr_lint import trace_plan_jaxpr
from repro.core import PAPER_STENCILS
from repro.core import plan as planmod

SWEEPS = 4
# a grain-aligned grid and tile: pad-free at 4 sweeps and at the
# remainders' 1 and 3
SPEC = PAPER_STENCILS["jacobi2d"]
SHAPE, TILE = (32, 384), (16, 128)


def _x64(dtype):
    return jax.enable_x64(np.dtype(dtype).itemsize == 8)


@functools.lru_cache(maxsize=None)
def _block(dtype, sweeps):
    """One jitted fused block of ``sweeps`` applications."""
    plan = planmod.lower(SPEC, SHAPE, jnp.dtype(dtype), backend="pallas",
                         sweeps=sweeps, tile=TILE)
    assert plan.ghost_strategy == "pad-free"
    return jax.jit(functools.partial(planmod.execute, plan))


def _chained(g, dtype, q, r):
    for _ in range(q):
        g = _block(dtype, SWEEPS)(g)
    return _block(dtype, r)(g) if r else g


@pytest.mark.parametrize("r", [0, 1, 3])
@pytest.mark.parametrize("q", [1, 2, 3, 5])
@pytest.mark.parametrize("batched", [False, True], ids=["single", "batch"])
@pytest.mark.parametrize("dtype", ["float64", "float32"])
def test_run_plan_is_bitwise_chained_blocks(dtype, batched, q, r, rng):
    iters = SWEEPS * q + r
    with _x64(dtype):
        if batched:
            gs = jnp.asarray(rng.standard_normal((2,) + SHAPE), dtype)
            run = planmod.batch_runner(SPEC, "pallas", SWEEPS, TILE, True)
            got = run(gs, iters=iters)
            want = jnp.stack([_chained(g, dtype, q, r) for g in gs])
        else:
            g = jnp.asarray(rng.standard_normal(SHAPE), dtype)
            plan = planmod.lower(SPEC, SHAPE, g.dtype, backend="pallas",
                                 sweeps=SWEEPS, tile=TILE)
            assert plan.blocks_per_scan_step == 2
            got = jax.jit(lambda x: planmod.run_plan(plan, x, iters))(g)
            want = _chained(g, dtype, q, r)
        assert got.dtype == jnp.dtype(dtype)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


@pytest.mark.parametrize("backend,shape,tile,on_mesh,strategy,blocks", [
    ("pallas", SHAPE, TILE, False, "pad-free", 2),
    ("pallas", (33, 47), "auto", False, "padded-window", 1),
    ("ref", SHAPE, None, False, "pad", 1),
    ("pallas", SHAPE, TILE, True, "padded-window", 1),
    ("ref", SHAPE, None, True, "pad", 1),
], ids=["pad-free", "padded-window", "ref", "mesh-pallas", "mesh-ref"])
def test_scan_step_runs_two_blocks_only_where_the_kernel_reads_the_carry(
        backend, shape, tile, on_mesh, strategy, blocks):
    """Only the single-device pad-free kernel reads the carried grid in
    place; the scan of every other plan keeps one block a step (a
    distributed kernel reads the exchanged window, and XLA could fuse
    two blocks of jnp code and change their f64 order)."""
    mesh, axes = ((jax.make_mesh((1, 1), ("sx", "sy")), ("sx", "sy"))
                  if on_mesh else (None, None))     # one device suffices
    plan = planmod.lower(SPEC, shape, jnp.float32, backend=backend,
                         sweeps=SWEEPS, tile=tile, mesh=mesh,
                         grid_axes=axes)
    assert plan.ghost_strategy == strategy
    assert plan.is_distributed == on_mesh
    assert plan.blocks_per_scan_step == blocks
    jaxpr = trace_plan_jaxpr(plan, iters=5 * SWEEPS).jaxpr
    (scan,) = [e for e in jaxpr.eqns if e.primitive.name == "scan"]
    assert scan.params["length"] == 5
    assert scan.params["unroll"] == blocks

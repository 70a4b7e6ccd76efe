"""Multi-stencil fusion pipelines: spec algebra, fused lowering, and
all-executor parity with the chained per-stage oracle.

The ground truth everywhere is the **chained f64 oracle**: apply each
stage's ``ref.apply_stencil`` in order, ``iters`` times.  The fused
plan (one widened-window pass per chain application) must reproduce it
f64 *bit-identically* through every executor — ref, Pallas, the
distributed shard_map path, and (to the dense-order reassociation
bound) the SPU VM.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import enable_x64
from jax.sharding import Mesh

import repro.core as rc
from repro.core import plan as _plan
from repro.core import vm as _vm
from repro.core.stencil import StencilPipeline, StencilSpec


def chained_oracle(pipe, g, iters=1):
    out = g
    for _ in range(iters):
        for s in pipe.stages:
            out = rc.apply_stencil(s, out)
    return out


def _grid(shape, rng):
    return jnp.asarray(rng.standard_normal(shape))


def _single_device_mesh():
    return Mesh(np.array(jax.devices()[:1]), ("sx",))


PIPES = {
    "reaction_diffusion2d": rc.reaction_diffusion2d(),   # reflect+reflect
    "advect_diffuse2d": rc.advect_diffuse2d(),           # periodic+periodic
    "zero_constant": StencilPipeline("zero_constant", (
        rc.jacobi2d(),
        rc.jacobi2d().with_boundary("constant(0.25)"))),
    "three_stage_mixed_radius": StencilPipeline("three_stage_mixed_radius", (
        rc.jacobi2d().with_boundary("reflect"),
        rc.blur2d().with_boundary("reflect"),        # separable, radius 2
        StencilSpec("wide", 2, (((0, 0), 0.5), ((-2, 0), 0.25),
                                ((0, 2), 0.25)), boundary="reflect"))),
    "pipe1d": StencilPipeline("pipe1d", (
        rc.jacobi1d().with_boundary("reflect"),
        rc.advect1d().with_boundary("zero"))),
}


# ---------------------------------------------------------------------------
# Spec algebra
# ---------------------------------------------------------------------------
def test_pipeline_halo_is_sum_of_stage_halos():
    p = PIPES["three_stage_mixed_radius"]
    assert p.halo == (5, 5)     # jacobi 1 + blur 2 + wide 2 per dim
    assert rc.reaction_diffusion2d().halo == (2, 2)


def test_pipeline_validation():
    with pytest.raises(ValueError):
        StencilPipeline("empty", ())
    with pytest.raises(ValueError):
        StencilPipeline("rank_mismatch", (rc.jacobi2d(), rc.jacobi1d()))
    with pytest.raises(TypeError):
        StencilPipeline("not_a_spec", (rc.jacobi2d(), "nope"))


def test_pipeline_fusability_rule():
    # homogeneous non-periodic and homogeneous periodic chains fuse;
    # mixing periodic with anything else does not (the wrap invariant
    # cannot be restored tile-locally next to a non-periodic stage)
    assert rc.reaction_diffusion2d().fusable
    assert rc.advect_diffuse2d().fusable
    assert PIPES["zero_constant"].fusable
    mixed = StencilPipeline("m", (rc.jacobi2d(), rc.advect2d()))
    assert not mixed.fusable


def test_pipeline_with_boundary_rebases_every_stage():
    p = rc.reaction_diffusion2d().with_boundary("zero")
    assert all(s.boundary == "zero" for s in p.stages)
    assert p.fusable


def test_as_stages():
    from repro.core import as_stages
    assert as_stages(rc.jacobi2d()) == (rc.jacobi2d(),)
    p = rc.reaction_diffusion2d()
    assert as_stages(p) == p.stages


def test_pipeline_program_assembles_per_stage():
    p = rc.reaction_diffusion2d()
    prog = rc.assemble_pipeline(p)
    assert prog.n_stages == 2
    assert prog.spec_name == p.name
    assert prog.n_instrs == sum(s.n_instrs for s in prog.stages)
    assert prog.words == (prog.stages[0].words + prog.stages[1].words)
    dic = prog.dynamic_instruction_count(1024)
    parts = [s.dynamic_instruction_count(1024) for s in prog.stages]
    assert dic["total"] == sum(p_["total"] for p_ in parts)
    assert rc.assemble_any(p).n_stages == 2
    assert rc.assemble_any(rc.jacobi2d()).spec_name == "jacobi2d"


# ---------------------------------------------------------------------------
# Fused lowering
# ---------------------------------------------------------------------------
def test_fused_plan_shape():
    p = rc.reaction_diffusion2d()
    plan = _plan.lower(p, (32, 64), np.float32, backend="pallas", sweeps=2)
    assert plan.is_pipeline and plan.fused
    assert plan.stages == p.stages
    assert plan.deep_halo == (4, 4)          # sweeps * sum of stage radii
    assert plan.boundary_mode == "reflect"


def test_unfusable_plan_lowers_staged():
    mixed = StencilPipeline("m2", (rc.jacobi2d(), rc.advect2d()))
    plan = _plan.lower(mixed, (32, 64), np.float32, backend="pallas")
    assert plan.is_pipeline and not plan.fused
    assert plan.ghost_strategy == "staged"
    # stage plans are real single-spec plans, lowered through the cache
    sp = plan.stage_plan(1)
    assert sp.spec == rc.advect2d() and not sp.is_pipeline


def test_pipeline_window_sweep_rejects_unfusable():
    from repro.kernels import engine as keng
    mixed = StencilPipeline("m3", (rc.jacobi2d(), rc.advect2d()))
    window = jnp.zeros(tuple(n + 2 * h for n, h in zip((16, 32),
                                                       mixed.halo)))
    with pytest.raises(ValueError, match="cannot\\s+run fused"):
        keng.stencil_window_sweep(mixed, window, (16, 32), (0, 0), (16, 32))


# ---------------------------------------------------------------------------
# Executor parity: f64 bit-identity with the chained oracle
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("name", sorted(PIPES))
@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_fused_matches_chained_oracle_f64(name, backend):
    p = PIPES[name]
    shape = (19,) if p.ndim == 1 else (14, 22)
    with enable_x64():
        g = _grid(shape, np.random.default_rng(3))
        want = np.asarray(chained_oracle(p, g, iters=3))
        plan = _plan.lower(p, shape, g.dtype, backend=backend, sweeps=1)
        got = np.asarray(_plan.run_plan(plan, g, 3))
        np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("name", ["reaction_diffusion2d", "advect_diffuse2d",
                                  "three_stage_mixed_radius"])
def test_fused_temporal_blocking_matches_oracle_f64(name):
    # sweeps=t fuses t whole-chain applications per widened window
    p = PIPES[name]
    with enable_x64():
        g = _grid((12, 18), np.random.default_rng(5))
        want = np.asarray(chained_oracle(p, g, iters=4))
        for backend in ("ref", "pallas"):
            plan = _plan.lower(p, g.shape, g.dtype, backend=backend,
                               sweeps=2)
            got = np.asarray(_plan.run_plan(plan, g, 4))
            np.testing.assert_array_equal(got, want)


def test_staged_fallback_matches_oracle_f64():
    mixed = StencilPipeline("m4", (rc.jacobi2d(), rc.advect2d(),
                                   rc.jacobi2d()))
    assert not mixed.fusable
    with enable_x64():
        g = _grid((13, 21), np.random.default_rng(7))
        want = np.asarray(chained_oracle(mixed, g, iters=2))
        for backend in ("ref", "pallas"):
            plan = _plan.lower(mixed, g.shape, g.dtype, backend=backend)
            got = np.asarray(_plan.run_plan(plan, g, 2))
            np.testing.assert_array_equal(got, want)


@pytest.mark.parametrize("name", sorted(PIPES))
def test_padfree_pipeline_matches_oracle_f64(name):
    # grain-aligned grid and tile: the fused chain DMAs its windows,
    # wrapped ghost slabs included, straight from the unpadded grid
    p = PIPES[name]
    shape, tile = ((4096,), (1024,)) if p.ndim == 1 else ((48, 384), (16, 128))
    with enable_x64():
        g = _grid(shape, np.random.default_rng(13))
        want = np.asarray(chained_oracle(p, g, iters=4))
        plan = _plan.lower(p, shape, g.dtype, backend="pallas", sweeps=2,
                           tile=tile)
        assert plan.ghost_strategy == "pad-free"
        np.testing.assert_array_equal(np.asarray(_plan.run_plan(plan, g, 4)),
                                      want)


@pytest.mark.parametrize("name", ["reaction_diffusion2d",
                                  "advect_diffuse2d"])
def test_distributed_matches_chained_oracle_f64(name):
    p = PIPES[name]
    with enable_x64():
        g = _grid((16, 24), np.random.default_rng(11))
        want = np.asarray(chained_oracle(p, g, iters=3))
        fn = rc.distributed_stencil_fn(p, _single_device_mesh(),
                                       ("sx", None), iters=3, sweeps=1)
        np.testing.assert_array_equal(np.asarray(fn(g)), want)


def test_vm_matches_chained_oracle():
    # the SPU VM runs each stage's dense tap program in stream-plan
    # order; vs the oracle's pinned factored order that is the usual
    # reassociation bound — the repo-wide VM contract, atol=1e-12
    with enable_x64():
        g = np.random.default_rng(13).standard_normal((12, 20))
        for name in ("reaction_diffusion2d", "three_stage_mixed_radius"):
            p = PIPES[name]
            want = np.asarray(chained_oracle(p, jnp.asarray(g), iters=2))
            plan = _plan.lower(p, g.shape, g.dtype, backend="vm")
            got, counters = _vm.execute_plan(plan, g, iters=2)
            np.testing.assert_allclose(got, want, atol=1e-12)
            assert counters.instructions > 0


def test_engine_accepts_pipeline():
    p = rc.reaction_diffusion2d()
    eng = rc.CasperEngine(p, backend="pallas", sweeps=2, tile="auto")
    assert eng.program.n_stages == 2
    with enable_x64():
        g = _grid((16, 24), np.random.default_rng(17))
        want = np.asarray(chained_oracle(p, g, iters=4))
        np.testing.assert_array_equal(np.asarray(eng.run(g, iters=4)), want)


def test_run_plan_remainder_decomposition():
    # iters = q*sweeps + r through the fused chain: 5 = 2*2 + 1
    p = rc.reaction_diffusion2d()
    with enable_x64():
        g = _grid((12, 18), np.random.default_rng(19))
        want = np.asarray(chained_oracle(p, g, iters=5))
        plan = _plan.lower(p, g.shape, g.dtype, backend="ref", sweeps=2)
        got = np.asarray(_plan.run_plan(plan, g, 5))
        np.testing.assert_array_equal(got, want)


# ---------------------------------------------------------------------------
# Traffic model
# ---------------------------------------------------------------------------
def test_hbm_pipeline_traffic_fused_below_staged():
    from repro.kernels import engine as keng
    for p in rc.PAPER_PIPELINES.values():
        t = keng.hbm_pipeline_traffic(p, (512, 512), tile=(32, 256))
        assert t["fused_bytes"] < t["staged_bytes"]
        assert t["reduction"] > 1.5
        # closed form: fused = n_tiles*(prod(tile+2H)+prod(tile))*4
        n_tiles = (512 // 32) * (512 // 256)
        want = n_tiles * ((32 + 4) * (256 + 4) + 32 * 256) * 4
        assert t["fused_bytes"] == float(want)


def test_pipeline_autotune_and_cost_model():
    from repro.core import perfmodel as pm
    from repro.kernels import tune
    p = rc.reaction_diffusion2d()
    res = tune.autotune(p, (256, 512))
    assert res.tile in dict(res.table)
    cost = pm.pallas_tile_cost(p, (256, 512), res.tile)
    assert np.isfinite(cost) and cost > 0
    # a tile that cannot hold the widened window in VMEM is infeasible
    assert pm.pallas_tile_cost(
        p, (1 << 14, 1 << 14), (8192, 8192)) == float("inf")


# ---------------------------------------------------------------------------
# A spec is the one-stage pipeline of itself
# ---------------------------------------------------------------------------
ONE_STAGE_BOUNDARIES = ("zero", "constant(0.75)", "periodic", "reflect")
#: Per rank: a benchmark-sized grid (ranked by the cost model only) and
#: a small grain-aligned one (also run through the kernel in f64).
ONE_STAGE_SHAPES = {1: ((1 << 24,), (4096,)),
                    2: ((16384, 16384), (32, 256)),
                    3: ((512, 512, 512), (8, 16, 128))}


@pytest.mark.parametrize("sweeps", [1, 2, 4])
@pytest.mark.parametrize("boundary", ONE_STAGE_BOUNDARIES)
@pytest.mark.parametrize("name", list(rc.PAPER_STENCILS))
def test_one_stage_pipeline_is_its_spec(name, boundary, sweeps):
    """A spec and the pipeline of that one spec take every decision
    below ``plan.lower`` alike: the tile cost at every candidate and
    wide tile, the quick-compile rule, the autotuned tile, the lowered
    tile and ghost strategy, and the fused kernel's f64 output, bit for
    bit."""
    from repro.core import perfmodel as pm
    from repro.kernels import engine as keng
    from repro.kernels import tune
    spec = rc.PAPER_STENCILS[name].with_boundary(boundary)
    pipe = StencilPipeline("one", (spec,))
    big, small = ONE_STAGE_SHAPES[spec.ndim]
    tiles = tune.CANDIDATE_TILES[spec.ndim] + tune.WIDE_TILES.get(
        spec.ndim, ())
    for shape in (big, small):
        for tile in tiles:
            assert pm.pallas_tile_cost(spec, shape, tile, sweeps) \
                == pm.pallas_tile_cost(pipe, shape, tile, sweeps)
        assert tune.autotune(spec, shape, sweeps).tile \
            == tune.autotune(pipe, shape, sweeps).tile
    for tile in tiles:
        assert pm.compiles_quickly(spec, tile, sweeps) \
            == pm.compiles_quickly(pipe, tile, sweeps)
    with enable_x64():
        plans = [_plan.lower(k, small, jnp.float64, backend="pallas",
                             sweeps=sweeps, tile="auto")
                 for k in (spec, pipe)]
        assert plans[0].tile == plans[1].tile
        assert plans[0].ghost_strategy == plans[1].ghost_strategy
        g = _grid(small, np.random.default_rng(23))
        got = keng.stencil_sweep(pipe, g, tile=plans[1].tile, sweeps=sweeps)
        want = keng.stencil_sweep(spec, g, tile=plans[0].tile, sweeps=sweeps)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))

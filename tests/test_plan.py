"""ExecutionPlan lowering pipeline + process-wide plan cache.

Covers the acceptance matrix of the plan refactor: lowering resolves
every decision once (ghost strategy, exchange strategy, tile,
decomposition, program); the cache keys on boundary + structure + dtype
+ sweeps + backend + mesh fingerprint; LRU eviction order; the
retrace-count guard (a second identical engine performs zero lowers and
zero autotune sweeps and reuses the same jitted runner); remainder
plans come from the cache (the old ``_build_step(r)`` re-autotune at
trace time is gone); all five backends execute plans; and the legacy
kernel shims warn.
"""
import warnings

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import CasperEngine, PAPER_STENCILS
from repro.core import plan as planmod
from repro.core import ref as cref
from repro.core import vm as vmmod
from repro.core.plan import (PLAN_CACHE, PlanCache, ExecutionPlan,
                             exchange_strategy_for, ghost_strategy_for,
                             lower, plan_cache_stats)


def _stats():
    return plan_cache_stats()


# ---------------------------------------------------------------------------
# Lowering resolves the decisions, once
# ---------------------------------------------------------------------------
def test_lower_resolves_everything_once():
    spec = PAPER_STENCILS["blur2d"]
    p = lower(spec, (64, 96), jnp.float32, backend="pallas", sweeps=3,
              tile="auto", interpret=True)
    assert isinstance(p, ExecutionPlan)
    assert p.halo == (2, 2) and p.deep_halo == (6, 6)
    assert p.tile is not None and len(p.tile) == 2     # autotuned, concrete
    assert p.ghost_strategy in ("pad-free", "padded-window")
    assert p.factorization.structure == "separable"
    assert p.program.spec_name == "blur2d"
    assert p.stream_plan.structure == "separable"
    assert p.decompose(10) == (3, 1)
    # the remainder plan comes from the same pipeline, narrower sweeps
    r = p.remainder(1)
    assert r.sweeps == 1 and r.spec == spec and r.tile is not None


def test_ghost_strategy_decision_lives_in_plan():
    spec = PAPER_STENCILS["jacobi2d"]
    # grid a multiple of a tile at least the aligned fetch depth:
    # pad-free, for every boundary mode; anything else: padded fallback
    for mode in ("zero", "periodic", "reflect"):
        s = spec.with_boundary(mode)
        assert ghost_strategy_for(s, (64, 256), 4, 1, (32, 128)) \
            == "pad-free"
        assert ghost_strategy_for(s, (70, 130), 4, 1, (32, 128)) \
            == "padded-window"
    assert ghost_strategy_for(spec, (3, 7), 4, 3, (32, 64)) \
        == "padded-window"
    # a tile shallower than its fetch depth (8 rows for 4 ghost layers
    # of f32) cannot wrap its ghost slabs
    assert ghost_strategy_for(spec, (64, 256), 4, 4, (8, 128)) \
        == "pad-free"
    assert ghost_strategy_for(spec, (64, 256), 4, 9, (8, 128)) \
        == "padded-window"
    # the oracle / vm backends record their strategies too
    assert lower(spec, (16, 16), jnp.float32).ghost_strategy == "pad"
    assert lower(spec, (16, 16), jnp.float32,
                 backend="vm").ghost_strategy == "stream"


def test_exchange_strategy_decision_lives_in_plan():
    assert exchange_strategy_for("zero") == "zero-fill"
    assert exchange_strategy_for("periodic") == "wrap-ring"
    assert exchange_strategy_for("constant") == "edge-fixup"
    assert exchange_strategy_for("reflect") == "edge-fixup"
    with pytest.raises(ValueError):
        exchange_strategy_for("mirror")


def test_lower_validation():
    spec = PAPER_STENCILS["jacobi2d"]
    with pytest.raises(ValueError):
        lower(spec, (16, 16), jnp.float32, backend="gpu")
    with pytest.raises(ValueError):
        lower(spec, (16, 16), jnp.float32, sweeps=0)
    with pytest.raises(ValueError):
        lower(spec, (16,), jnp.float32)                 # rank mismatch
    with pytest.raises(ValueError):
        lower(spec, (16, 16), jnp.float32, grid_axes=("sx", None))  # no mesh


# ---------------------------------------------------------------------------
# Cache: hit/miss counters, key coverage, eviction order
# ---------------------------------------------------------------------------
def test_cache_hit_miss_counters():
    spec = PAPER_STENCILS["jacobi1d"]
    s0 = _stats()
    p1 = lower(spec, (333,), jnp.float32, backend="ref", sweeps=2)
    s1 = _stats()
    assert s1["misses"] == s0["misses"] + 1
    assert s1["lowers"] == s0["lowers"] + 1
    p2 = lower(spec, (333,), jnp.float32, backend="ref", sweeps=2)
    s2 = _stats()
    assert p2 is p1                              # the same cached object
    assert s2["hits"] == s1["hits"] + 1
    assert s2["lowers"] == s1["lowers"]          # no re-lower


def test_cache_key_includes_boundary_structure_dtype_sweeps_backend():
    spec = PAPER_STENCILS["blur2d"]
    base = lower(spec, (40, 48), jnp.float32, backend="ref", sweeps=2)
    variants = [
        lower(spec.with_boundary("periodic"), (40, 48), jnp.float32,
              backend="ref", sweeps=2),
        lower(spec.with_structure("dense"), (40, 48), jnp.float32,
              backend="ref", sweeps=2),
        lower(spec, (40, 48), jnp.float64, backend="ref", sweeps=2),
        lower(spec, (40, 48), jnp.float32, backend="ref", sweeps=3),
        lower(spec, (40, 48), jnp.float32, backend="vm", sweeps=2),
        lower(spec, (40, 48), jnp.float32, backend="pallas", sweeps=2),
        lower(spec, (48, 40), jnp.float32, backend="ref", sweeps=2),
    ]
    plans = [base] + variants
    assert len({id(p) for p in plans}) == len(plans)
    assert variants[0].boundary_mode == "periodic"
    assert variants[1].factorization.terms is None
    assert variants[2].dtype == "float64"


def test_cache_key_includes_mesh_fingerprint():
    spec = PAPER_STENCILS["jacobi1d"]
    mesh = jax.make_mesh((1,), ("sx",))
    single = lower(spec, (64,), jnp.float32, backend="ref", sweeps=2)
    dist = lower(spec, (64,), jnp.float32, backend="ref", sweeps=2,
                 mesh=mesh, grid_axes=("sx",))
    assert dist is not single
    assert dist.mesh_fingerprint is not None
    assert single.mesh_fingerprint is None
    # the fingerprint pins the exact device assignment: a mesh over
    # different (or reordered) devices must not alias this plan's Mesh
    assert dist.mesh_fingerprint[2] == tuple(
        d.id for d in mesh.devices.flat)
    assert dist.exchange == ("zero-fill",)
    assert dist.shard_shape == (64,)
    # same fingerprint -> cache hit
    s0 = _stats()
    again = lower(spec, (64,), jnp.float32, backend="ref", sweeps=2,
                  mesh=mesh, grid_axes=("sx",))
    assert again is dist
    assert _stats()["lowers"] == s0["lowers"]


def test_cache_eviction_order_is_lru():
    cache = PlanCache(maxsize=2)
    cache.put("a", 1)
    cache.put("b", 2)
    assert cache.get("a") == 1                   # refresh a: b is now LRU
    cache.put("c", 3)                            # evicts b
    assert cache.keys() == ["a", "c"]
    assert cache.get("b") is None
    assert cache.evictions == 1
    st = cache.stats()
    assert st["size"] == 2 and st["hits"] == 1 and st["misses"] == 1
    cache.clear()
    assert len(cache) == 0 and cache.stats()["hits"] == 0


# ---------------------------------------------------------------------------
# Set-up events: each lowering step is a /casper/ duration event
# ---------------------------------------------------------------------------
class _Events:
    """The ``/casper/`` duration events recorded while open."""

    def __init__(self):
        self.names = []

    def _listen(self, event, duration, **_):
        if event.startswith("/casper/"):
            assert duration >= 0
            self.names.append(event)

    def __enter__(self):
        jax.monitoring.register_event_duration_secs_listener(self._listen)
        return self

    def __exit__(self, *exc):
        jax.monitoring.unregister_event_duration_listener(self._listen)


@pytest.mark.parametrize("tile,autotunes", [("auto", 1), ((8, 128), 0)])
def test_cold_lower_records_one_lower_event_warm_none(tile, autotunes):
    spec = PAPER_STENCILS["jacobi2d"].with_boundary("constant(0.5)")
    shape = (48, 136) if tile == "auto" else (56, 136)   # fresh keys
    with _Events() as cold:
        lower(spec, shape, jnp.float32, backend="pallas", sweeps=2,
              tile=tile, interpret=True)
    assert sorted(cold.names) == sorted(
        ["/casper/casper.lower", "/casper/casper.verify"]
        + ["/casper/casper.autotune"] * autotunes)
    with _Events() as warm:
        lower(spec, shape, jnp.float32, backend="pallas", sweeps=2,
              tile=tile, interpret=True)
    assert warm.names == []


def test_engine_run_records_no_event_once_lowered(rng):
    """The hot path (``CasperEngine.run``) is a profiler span only."""
    spec = PAPER_STENCILS["heat3d"].with_boundary("reflect")
    g = jnp.asarray(rng.standard_normal((8, 12, 40)), jnp.float32)
    eng = CasperEngine(spec, backend="pallas", sweeps=2, tile="auto")
    with _Events() as first:
        eng.run(g, iters=5).block_until_ready()
    assert first.names.count("/casper/casper.lower") == 2   # sweeps 2, 1
    with _Events() as again:
        eng.run(g, iters=5).block_until_ready()
    assert again.names == []


# ---------------------------------------------------------------------------
# Retrace guard: second engine = zero lowers, zero autotunes, same runner
# ---------------------------------------------------------------------------
def test_second_identical_engine_zero_lowers_zero_autotunes(rng):
    spec = PAPER_STENCILS["jacobi2d"]
    g = jnp.asarray(rng.standard_normal((40, 72)), jnp.float32)
    eng1 = CasperEngine(spec, backend="pallas", sweeps=3, tile="auto")
    out1 = eng1.run(g, iters=7)                  # q=2, r=1: remainder too
    s0 = _stats()
    eng2 = CasperEngine(spec, backend="pallas", sweeps=3, tile="auto")
    out2 = eng2.run(g, iters=7)
    s1 = _stats()
    assert s1["lowers"] == s0["lowers"], "second engine re-lowered"
    assert s1["autotune_calls"] == s0["autotune_calls"], \
        "second engine re-autotuned"
    # zero retraces: the jitted runner is the same process-wide callable
    # (eng2.run never even re-traced, hence zero lookups above)
    assert eng2._run_jit is eng1._run_jit
    # an explicit lowering for the same configuration is a pure cache hit
    p = eng2.plan_for(g.shape, g.dtype)
    s2 = _stats()
    assert s2["hits"] == s1["hits"] + 1
    assert s2["lowers"] == s1["lowers"]
    assert p is eng1.plan_for(g.shape, g.dtype)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


def test_remainder_plans_come_from_cache(rng):
    """The old ``_build_step(r)`` re-resolved ``tile="auto"`` (a full
    autotune sweep) per distinct remainder at trace time; remainder
    plans now come from the plan cache like everything else."""
    spec = PAPER_STENCILS["heat3d"]
    g = jnp.asarray(rng.standard_normal((8, 12, 24)), jnp.float32)
    eng = CasperEngine(spec, backend="pallas", sweeps=4, tile="auto")
    eng.run(g, iters=9)                          # lowers sweeps=4 and r=1
    s0 = _stats()
    eng.run(g, iters=13)                         # r=1 again: all cached
    s1 = _stats()
    assert s1["lowers"] == s0["lowers"]
    assert s1["autotune_calls"] == s0["autotune_calls"]
    # a *new* remainder width lowers exactly one new plan (from cache
    # thereafter), never more
    eng.run(g, iters=10)                         # r=2: one new plan
    s2 = _stats()
    assert s2["lowers"] == s1["lowers"] + 1
    eng.run(g, iters=14)                         # r=2 again: cached
    assert _stats()["lowers"] == s2["lowers"]


# ---------------------------------------------------------------------------
# Pipeline plans live in the same cache
# ---------------------------------------------------------------------------
def test_pipeline_cache_key_stage_order_and_boundary_distinct():
    """Pipelines keyed on their full stage tuple: permuting the stage
    order or any single stage's boundary is a different plan (same
    pipeline *name* throughout, so distinctness comes from the stages,
    not the label)."""
    from repro.core import StencilPipeline
    a = PAPER_STENCILS["jacobi2d"].with_boundary("reflect")
    b = PAPER_STENCILS["blur2d"].with_boundary("reflect")
    perms = [
        StencilPipeline("perm", (a, b)),
        StencilPipeline("perm", (b, a)),                       # order swap
        StencilPipeline("perm", (a.with_boundary("zero"), b)),
        StencilPipeline("perm", (a, b.with_boundary("zero"))),
        StencilPipeline("perm", (a.with_boundary("zero"),
                                 b.with_boundary("zero"))),
    ]
    s0 = _stats()
    plans = [lower(p, (24, 40), jnp.float64, backend="ref", sweeps=1)
             for p in perms]
    s1 = _stats()
    assert len({id(p) for p in plans}) == len(plans)
    assert s1["lowers"] == s0["lowers"] + len(perms)
    # same stages again -> pure hit, the very same cached object
    again = lower(StencilPipeline("perm", (a, b)), (24, 40), jnp.float64,
                  backend="ref", sweeps=1)
    s2 = _stats()
    assert again is plans[0]
    assert s2["lowers"] == s1["lowers"]
    assert s2["hits"] == s1["hits"] + 1


def test_cache_eviction_lru_with_pipeline_plans_interleaved():
    """Pipeline and single-spec plans share one LRU: their keys hash
    side by side and evict in pure recency order regardless of kind."""
    from repro.core import StencilPipeline
    spec = PAPER_STENCILS["jacobi2d"]
    pipe = StencilPipeline("evict_p", (spec, spec.with_boundary("reflect")))
    k_spec = planmod.plan_key(spec, (16, 16), jnp.float32, "ref", 1,
                              None, False)
    k_pipe = planmod.plan_key(pipe, (16, 16), jnp.float32, "ref", 1,
                              None, False)
    k_pipe2 = planmod.plan_key(pipe, (16, 16), jnp.float32, "ref", 2,
                               None, False)
    assert len({k_spec, k_pipe, k_pipe2}) == 3
    cache = PlanCache(maxsize=2)
    cache.put(k_spec, "spec-plan")
    cache.put(k_pipe, "pipe-plan")
    assert cache.get(k_spec) == "spec-plan"      # refresh: pipe is LRU
    cache.put(k_pipe2, "pipe-plan-2")            # evicts k_pipe
    assert cache.keys() == [k_spec, k_pipe2]
    assert cache.get(k_pipe) is None
    assert cache.evictions == 1
    # and the other way round: the single-spec plan evicts first when
    # the pipeline plans are the recent ones
    cache.get(k_pipe2)                           # spec is LRU now
    cache.put(k_pipe, "pipe-plan")               # evicts k_spec
    assert cache.keys() == [k_pipe2, k_pipe]
    assert cache.evictions == 2


def test_second_identical_pipeline_engine_zero_lowers_zero_autotunes(rng):
    from repro.core import reaction_diffusion2d
    pipe = reaction_diffusion2d()
    g = jnp.asarray(rng.standard_normal((24, 40)), jnp.float32)
    eng1 = CasperEngine(pipe, backend="pallas", sweeps=2, tile="auto")
    out1 = eng1.run(g, iters=5)                  # q=2, r=1: remainder too
    s0 = _stats()
    eng2 = CasperEngine(pipe, backend="pallas", sweeps=2, tile="auto")
    out2 = eng2.run(g, iters=5)
    s1 = _stats()
    assert s1["lowers"] == s0["lowers"], "second pipeline engine re-lowered"
    assert s1["autotune_calls"] == s0["autotune_calls"], \
        "second pipeline engine re-autotuned"
    assert eng2._run_jit is eng1._run_jit
    p = eng2.plan_for(g.shape, g.dtype)
    s2 = _stats()
    assert s2["hits"] == s1["hits"] + 1
    assert s2["lowers"] == s1["lowers"]
    assert p.is_pipeline and p is eng1.plan_for(g.shape, g.dtype)
    np.testing.assert_array_equal(np.asarray(out1), np.asarray(out2))


# ---------------------------------------------------------------------------
# All four backends consume a plan
# ---------------------------------------------------------------------------
def test_ref_backend_executes_plan(rng):
    spec = PAPER_STENCILS["jacobi2d"].with_boundary("reflect")
    g = jnp.asarray(rng.standard_normal((33, 47)), jnp.float32)
    p = lower(spec, g.shape, g.dtype, backend="ref", sweeps=3)
    got = cref.execute_plan(p, g)
    want = cref.run_iterations(spec, g, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    with pytest.raises(ValueError):
        cref.execute_plan(lower(spec, g.shape, g.dtype, backend="vm"), g)


def test_pallas_backend_executes_plan(rng):
    from repro.kernels import engine as keng
    spec = PAPER_STENCILS["blur2d"]
    g = jnp.asarray(rng.standard_normal((40, 56)), jnp.float32)
    p = lower(spec, g.shape, g.dtype, backend="pallas", sweeps=2,
              tile="auto")
    got = keng.execute_plan(p, g)
    want = cref.run_iterations(spec, g, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    with pytest.raises(ValueError):
        keng.execute_plan(lower(spec, g.shape, g.dtype, backend="ref"), g)


def test_vm_backend_executes_plan(rng):
    spec = PAPER_STENCILS["jacobi1d"].with_boundary("periodic")
    g = rng.standard_normal(64).astype(np.float32)
    p = lower(spec, g.shape, g.dtype, backend="vm", sweeps=2)
    out, counters = vmmod.execute_plan(p, g)
    want = np.asarray(cref.run_iterations(spec, jnp.asarray(g), 2))
    np.testing.assert_allclose(out, want, atol=1e-6)
    assert counters.instructions > 0
    with pytest.raises(ValueError):
        vmmod.execute_plan(lower(spec, g.shape, g.dtype, backend="ref"), g)


def test_distributed_backend_executes_plan(rng):
    """halo.execute_plan runs one fused distributed step from a lowered
    plan (single-device mesh keeps this in-process; multi-device runs in
    tests/test_distributed.py)."""
    from repro.core import halo as halomod
    spec = PAPER_STENCILS["jacobi1d"].with_boundary("periodic")
    mesh = jax.make_mesh((1,), ("sx",))
    g = jnp.asarray(rng.standard_normal((48,)), jnp.float32)
    p = lower(spec, g.shape, g.dtype, backend="ref", sweeps=2,
              mesh=mesh, grid_axes=("sx",))
    assert p.exchange == ("wrap-ring",)
    got = halomod.execute_plan(p, g)
    want = cref.run_iterations(spec, g, 2)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)
    with pytest.raises(ValueError):
        halomod.execute_plan(lower(spec, g.shape, g.dtype), g)


def test_run_plan_decomposition_matches_oracle(rng):
    spec = PAPER_STENCILS["jacobi2d"]
    g = jnp.asarray(rng.standard_normal((32, 48)), jnp.float32)
    p = lower(spec, g.shape, g.dtype, backend="ref", sweeps=3)
    got = jax.jit(lambda x: planmod.run_plan(p, x, 8))(g)
    want = cref.run_iterations(spec, g, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-6)


# ---------------------------------------------------------------------------
# Legacy shims: still work, now warn
# ---------------------------------------------------------------------------
def test_legacy_kernels_ref_module_warns(rng):
    from repro.kernels import ref as kref
    with pytest.warns(DeprecationWarning, match="stencil_ref"):
        fn = kref.stencil_ref
    assert fn is cref.apply_stencil
    with pytest.warns(DeprecationWarning, match="swa_ref"):
        fn = kref.swa_ref
    from repro.kernels.swa import swa_ref
    assert fn is swa_ref
    with pytest.warns(DeprecationWarning, match="StencilSpec"):
        kref.StencilSpec
    with pytest.raises(AttributeError):
        kref.no_such_name


def test_legacy_rank_shims_warn_and_match_engine(rng):
    import repro.kernels as kernels
    spec = PAPER_STENCILS["jacobi2d"]
    g = jnp.asarray(rng.standard_normal((40, 48)), jnp.float32)
    with pytest.warns(DeprecationWarning, match="stencil2d"):
        got = kernels.stencil2d(spec, g)
    want = cref.apply_stencil(spec, g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    g1 = jnp.asarray(rng.standard_normal((300,)), jnp.float32)
    with pytest.warns(DeprecationWarning, match="stencil1d"):
        got1 = kernels.stencil1d(PAPER_STENCILS["jacobi1d"], g1)
    np.testing.assert_allclose(
        np.asarray(got1),
        np.asarray(cref.apply_stencil(PAPER_STENCILS["jacobi1d"], g1)),
        atol=1e-5)


def test_new_homes_do_not_warn(rng):
    from repro.kernels.swa import swa_ref            # noqa: F401
    from repro.kernels import engine as keng
    spec = PAPER_STENCILS["jacobi1d"]
    g = jnp.asarray(rng.standard_normal((128,)), jnp.float32)
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        keng.stencil_apply(spec, g)
        cref.apply_stencil(spec, g)
    ours = [w for w in rec if "repro.kernels" in str(w.message)]
    assert not ours, [str(w.message) for w in ours]

"""Unified N-D temporal-blocking engine vs the core.ref oracle.

Covers the acceptance matrix of the engine refactor: every paper stencil
at ranks 1-3, ``sweeps`` in {1, 2, 4} against ``t`` chained reference
applications, the batched (leading-dim vmap) path, non-divisible grid
shapes, f64 bit-identity, and the autotuner/CasperEngine wiring.
"""
import math

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import CasperEngine, PAPER_PIPELINES, PAPER_STENCILS
from repro.core import plan
from repro.core import perfmodel as pm
from repro.core import ref as cref
from repro.kernels import engine, tune

# Small odd shapes: non-divisible by every candidate tile on every axis.
SHAPES = {1: (1000,), 2: (70, 130), 3: (9, 20, 150)}
TINY = {1: (5,), 2: (3, 7), 3: (2, 3, 5)}


def _chained(spec, g, t):
    return jax.jit(lambda x: cref.run_iterations(spec, x, t))(g)


@pytest.mark.parametrize("name", list(PAPER_STENCILS))
@pytest.mark.parametrize("sweeps", [1, 2, 4])
def test_fused_sweeps_match_chained_reference(name, sweeps, rng):
    spec = PAPER_STENCILS[name]
    g = jnp.asarray(rng.standard_normal(SHAPES[spec.ndim]), jnp.float32)
    got = engine.stencil_apply(spec, g, sweeps=sweeps)
    want = _chained(spec, g, sweeps)
    assert got.dtype == g.dtype
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)


@pytest.mark.parametrize("name", ["jacobi1d", "blur2d", "star33_3d"])
def test_grids_smaller_than_halo_window(name, rng):
    """Grids smaller than one tile and than the widened t*halo window."""
    spec = PAPER_STENCILS[name]
    g = jnp.asarray(rng.standard_normal(TINY[spec.ndim]), jnp.float32)
    got = engine.stencil_apply(spec, g, sweeps=3)
    want = _chained(spec, g, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)


@pytest.mark.parametrize("name", ["jacobi1d", "jacobi2d", "heat3d"])
def test_batched_leading_dim(name, rng):
    spec = PAPER_STENCILS[name]
    shape = (3,) + tuple(s // 2 for s in SHAPES[spec.ndim])
    g = jnp.asarray(rng.standard_normal(shape), jnp.float32)
    got = engine.stencil_apply(spec, g, sweeps=2)
    want = jnp.stack([_chained(spec, g[i], 2) for i in range(shape[0])])
    assert got.shape == g.shape
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=1e-5)


# Grain-aligned (grid, tile) per rank: each extent a multiple of the
# tile, each tile at least the aligned fetch depth, so the kernel takes
# the pad-free DMA path (SHAPES take the padded-window fallback).
PADFREE = {1: ((4096,), (1024,)), 2: ((32, 384), (16, 128)),
           3: ((16, 24, 384), (8, 8, 128))}


@pytest.mark.parametrize("strategy", ["padded-window", "pad-free"])
@pytest.mark.parametrize("name", list(PAPER_STENCILS))
def test_f64_bit_identical_to_oracle(name, strategy, rng):
    """sweeps=1 f64 output is bit-identical to the core.ref oracle in
    every evaluation form — eager, jitted, and the pure-numpy oracle —
    under both kernel strategies, because ref.tap_sum pins the
    accumulation order (XLA otherwise regroups add chains differently
    per compiled program)."""
    from jax import enable_x64
    spec = PAPER_STENCILS[name]
    shape, tile = ((SHAPES[spec.ndim], None) if strategy == "padded-window"
                   else PADFREE[spec.ndim])
    with enable_x64():
        g = jnp.asarray(rng.standard_normal(shape), jnp.float64)
        assert plan.ghost_strategy_for(spec, g.shape, g.dtype.itemsize, 1,
                                       tile) == strategy
        got = engine.stencil_apply(spec, g, tile=tile)
        assert got.dtype == jnp.float64
        assert bool(jnp.all(got == cref.apply_stencil(spec, g))), name
        assert bool(jnp.all(
            got == jax.jit(lambda x: cref.apply_stencil(spec, x))(g))), name
        np.testing.assert_array_equal(
            np.asarray(got), cref.apply_stencil_numpy(spec, np.asarray(g)))


def _kernel_tags(fn, *args):
    """``(name, metadata)`` of every ``pallas_call`` ``fn`` traces to."""
    from repro.analysis.jaxpr_lint import _walk_eqns
    jaxpr = jax.make_jaxpr(fn)(*args).jaxpr
    return [(str(e.params["name"]), dict(e.params["metadata"]))
            for e in _walk_eqns(jaxpr) if e.primitive.name == "pallas_call"]


# (stencil, grid, tile, sweeps=2) -> (strategy, grid steps, fetched
# window per step, written tile per step).  A pad-free window is
# tile + 2*ceil_to(sweeps*halo, grain) per dim (grain (8, 128) in 2-D,
# (1, 8, 128) in 3-D); a padded window is ceil_to(tile + 2*sweeps*halo,
# grain) per dim.
KERNEL_TAG_CASES = {
    "2d-pad-free": (("jacobi2d", (64, 512), (32, 256)),
                    ("pad-free", 2 * 2, (32 + 2 * 8) * (256 + 2 * 128),
                     32 * 256)),
    "3d-pad-free": (("heat3d", (16, 16, 256), (8, 8, 128)),
                    ("pad-free", 2 * 2 * 2,
                     (8 + 2 * 2) * (8 + 2 * 8) * (128 + 2 * 128),
                     8 * 8 * 128)),
    "padded-window": (("jacobi2d", (70, 130), (32, 256)),
                      ("window", 3 * 1, 40 * 384, 32 * 256)),
    "vmapped-bucket": (("jacobi2d", (3, 64, 512), (32, 256)),
                       ("pad-free", 3 * 2 * 2,
                        (32 + 2 * 8) * (256 + 2 * 128), 32 * 256)),
}


@pytest.mark.parametrize("case", list(KERNEL_TAG_CASES))
def test_kernel_tag_counts_what_the_kernel_moves(case):
    """Every fused kernel is named and tagged with its strategy, sweeps,
    tile, grid steps and the HBM bytes one execution fetches and
    writes (float32: 4 bytes a point)."""
    (name, shape, tile), (strategy, steps, window, out) = (
        KERNEL_TAG_CASES[case])
    spec = PAPER_STENCILS[name]
    g = jnp.zeros(shape, jnp.float32)
    tags = _kernel_tags(lambda x: engine.stencil_apply(
        spec, x, tile=tile, sweeps=2, interpret=True), g)
    assert tags == [("casper_fused", {
        "casper": "fused", "strategy": strategy, "sweeps": "2",
        "tile": "x".join(map(str, tile)), "grid_steps": str(steps),
        "fetch_bytes": str(steps * window * 4),
        "write_bytes": str(steps * out * 4)})]


def test_f64_fused_sweeps_bit_identical(rng):
    from jax import enable_x64
    spec = PAPER_STENCILS["jacobi2d"]
    with enable_x64():
        g = jnp.asarray(rng.standard_normal((70, 130)), jnp.float64)
        got = engine.stencil_apply(spec, g, sweeps=4)
        want = jax.jit(lambda x: cref.run_iterations(spec, x, 4))(g)
        assert bool(jnp.all(got == want))


def test_run_sweeps_remainder_decomposition(rng):
    """iters = q*sweeps + r is exact for non-divisible iters."""
    spec = PAPER_STENCILS["jacobi2d"]
    g = jnp.asarray(rng.standard_normal((64, 96)), jnp.float32)
    got = engine.run_sweeps(spec, g, iters=7, sweeps=3)
    want = _chained(spec, g, 7)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_rank_and_sweeps_validation(rng):
    spec = PAPER_STENCILS["jacobi2d"]
    g = jnp.zeros((8, 8, 8, 8), jnp.float32)
    with pytest.raises(ValueError):
        engine.stencil_apply(spec, g)        # rank ndim+2
    with pytest.raises(ValueError):
        engine.stencil_sweep(spec, jnp.zeros((8, 8)), sweeps=0)
    with pytest.raises(ValueError):
        engine.stencil_sweep(spec, jnp.zeros((8, 8)), tile=(8,))


@pytest.mark.parametrize("name", list(PAPER_STENCILS))
@pytest.mark.parametrize("sweeps", [1, 4])
def test_autotuner_picks_feasible_aligned_tile(name, sweeps):
    spec = PAPER_STENCILS[name]
    shape = SHAPES[spec.ndim]
    res = tune.autotune(spec, shape, sweeps=sweeps)
    assert len(res.tile) == spec.ndim
    assert np.isfinite(res.cost_s)
    assert res.tile[-1] % 128 == 0 or spec.ndim == 1
    # the chosen tile's cost is minimal over the candidate table
    assert res.cost_s == min(c for _, c in res.table)
    # feasibility under the VMEM model
    assert np.isfinite(pm.pallas_tile_cost(spec, shape, res.tile,
                                           sweeps=sweeps))


def test_autotuned_tile_correctness(rng):
    spec = PAPER_STENCILS["heat3d"]
    g = jnp.asarray(rng.standard_normal((9, 20, 150)), jnp.float32)
    res = tune.autotune(spec, g.shape, sweeps=2)
    got = engine.stencil_apply(spec, g, tile=res.tile, sweeps=2)
    np.testing.assert_allclose(np.asarray(got),
                               np.asarray(_chained(spec, g, 2)), atol=1e-5)


@pytest.mark.parametrize("name,shape,sweeps,strategy,max_steps", [
    # the benchmark's cells: a wide tile
    ("jacobi2d", (16384, 16384), 4, "pad-free", 2048),
    ("heat3d", (512, 512, 512), 4, "pad-free", 2048),
    # small grids and the serving buckets' element shapes
    ("jacobi2d", (256, 256), 4, "pad-free", None),
    ("heat3d", (8, 16, 128), 4, "pad-free", None),
    ("jacobi2d", (512, 512), 2, "pad-free", None),
    ("heat3d", (8, 16, 256), 2, "pad-free", None),
    # no tile of 128 lanes or more that divides it beats one padded
    # step: (64, 256) timed 4.7 us a call on the chip, (32, 128) 5.9 us
    ("jacobi2d", (64, 128), 4, "padded-window", None),
])
def test_autotune_resolves_tile_by_shape(name, shape, sweeps, strategy,
                                         max_steps):
    spec = PAPER_STENCILS[name]
    tile = tune.autotune(spec, shape, sweeps=sweeps).tile
    assert np.isfinite(pm.pallas_tile_cost(spec, shape, tile,
                                           sweeps=sweeps))
    assert plan.ghost_strategy_for(spec, shape, 4, sweeps, tile) == strategy
    if strategy == "pad-free":
        assert all(n % t == 0 for n, t in zip(shape, tile))
    if max_steps is not None:
        steps = math.prod(n // t for n, t in zip(shape, tile))
        assert tile in tune.WIDE_TILES[spec.ndim]
        assert steps <= max_steps


@pytest.mark.parametrize("name,boundary,shape,sweeps,wide", [
    ("jacobi2d", "zero", (4096, 4096), 4, True),
    ("blur2d", "zero", (4096, 4096), 4, True),
    ("jacobi2d", "reflect", (4096, 4096), 1, True),
    # bodies Mosaic compiles slowly keep the narrow tiles
    ("jacobi2d", "reflect", (4096, 4096), 4, False),
    ("heat3d", "reflect", (256, 256, 256), 4, False),
    ("star33_3d", "zero", (256, 256, 256), 4, False),
    ("reaction_diffusion2d", None, (4096, 4096), 4, False),
    ("advect_diffuse2d", None, (4096, 4096), 4, True),
])
def test_wide_tiles_only_for_quickly_compiled_bodies(name, boundary, shape,
                                                     sweeps, wide):
    """A wide tile is offered only where the kernel body stays under
    the vreg-operation cap and re-mirrors no reflect ghosts between
    sweeps (a pipeline's reflect stage counts); the narrow tiles are
    offered to every kernel."""
    if boundary is None:
        kernel = PAPER_PIPELINES[name]
        tile = tune.autotune(kernel, shape, sweeps=sweeps).tile
    else:
        kernel = PAPER_STENCILS[name].with_boundary(boundary)
        tile = tune.autotune(kernel, shape, sweeps=sweeps).tile
    offered = tune.candidate_tiles(len(shape), shape, 4, kernel, sweeps)
    assert set(tune.CANDIDATE_TILES[len(shape)]) <= set(offered)
    assert (tile in tune.WIDE_TILES[len(shape)]) is wide
    assert any(t in offered for t in tune.WIDE_TILES[len(shape)]) is wide


#: One fused call timed on a TPU v5e (µs; f32, zero boundary; a grid
#: under 1M points timed as a loop of 200 calls): the sweep the tile
#: model's constants were fitted to.
CHIP_TIMED_US = {
    ("jacobi2d", (16384, 16384), 4): {
        (32, 512): 33161.1, (64, 512): 19381.4, (64, 1024): 16066.5,
        (128, 512): 16074.9, (128, 1024): 13569.6, (128, 2048): 12185.6,
        (256, 1024): 12536.1, (256, 2048): 11572.8, (32, 1024): 19908.0,
        (32, 2048): 16051.7, (64, 2048): 13435.8, (256, 512): 14193.9},
    ("jacobi2d", (16384, 16384), 1): {(32, 512): 20510.5,
                                      (128, 1024): 7746.1},
    ("jacobi2d", (16384, 16384), 2): {(32, 512): 25164.9,
                                      (128, 1024): 9670.6},
    ("heat3d", (512, 512, 512), 4): {
        (8, 16, 128): 29344.8, (8, 32, 256): 16068.7,
        (16, 32, 256): 12906.6, (8, 32, 512): 13154.5,
        (16, 32, 512): 10721.8, (8, 64, 512): 11944.7,
        (16, 64, 256): 11798.5, (8, 16, 256): 20061.9,
        (8, 16, 512): 16100.8, (8, 32, 128): 22267.9,
        (4, 32, 512): 17785.7},
    ("heat3d", (512, 512, 512), 1): {(8, 16, 128): 15683.6,
                                     (16, 32, 256): 6134.7},
    ("heat3d", (512, 512, 512), 2): {(8, 16, 128): 19566.9,
                                     (16, 32, 256): 8210.3},
    ("jacobi2d", (64, 128), 4): {(8, 128): 10.5, (16, 128): 7.1,
                                 (32, 128): 5.9, (64, 256): 4.7},
    ("jacobi2d", (256, 256), 4): {(32, 256): 11.4, (64, 256): 8.4,
                                  (128, 512): 8.8, (256, 1024): 12.0},
    ("jacobi2d", (512, 512), 2): {(32, 512): 15.0, (64, 512): 9.9,
                                  (128, 512): 9.1, (128, 1024): 12.7},
    ("heat3d", (8, 16, 128), 4): {(8, 16, 128): 5.6, (8, 32, 256): 8.4},
}


@pytest.mark.parametrize("case", list(CHIP_TIMED_US))
def test_tile_model_ranks_the_chip_timings(case):
    """Among the candidate tiles timed on the chip, the one the cost
    model ranks first is within 3% of the fastest; where the autotuner's
    pick was timed, it is that tile.  On the grids the constants were
    fitted to, every timed tile's predicted time is within 20%."""
    name, shape, sweeps = case
    spec = PAPER_STENCILS[name].with_boundary("zero")
    if math.prod(shape) >= 1 << 20:
        for tile, us in CHIP_TIMED_US[case].items():
            model_us = pm.pallas_tile_cost(spec, shape, tile,
                                           sweeps=sweeps) * 1e6
            assert abs(model_us / us - 1) <= 0.2, (tile, model_us, us)
    cands = tune.candidate_tiles(spec.ndim, shape, 4, spec, sweeps)
    timed = {t: us for t, us in CHIP_TIMED_US[case].items() if t in cands}
    pick = min(timed, key=lambda t: pm.pallas_tile_cost(spec, shape, t,
                                                        sweeps=sweeps))
    assert timed[pick] <= 1.03 * min(timed.values())
    auto = tune.autotune(spec, shape, sweeps=sweeps).tile
    assert auto == pick or auto not in CHIP_TIMED_US[case]


def test_hbm_traffic_model_monotone():
    """Fused traffic reduction grows with sweeps; the pad-free fused
    path beats the padded-pipeline baseline even at t=1 (the unfused
    side pays the per-sweep host pad copy the fused side no longer
    does), and the window saving alone stays below t."""
    spec = PAPER_STENCILS["jacobi2d"]
    tms = [engine.hbm_traffic(spec, (2048, 2048), sweeps=t)
           for t in (1, 2, 4, 8)]
    reds = [tm["reduction"] for tm in tms]
    assert reds[0] > 1.0                      # pad copy charged to unfused
    assert all(b > a for a, b in zip(reds, reds[1:]))
    for t, tm in zip((1, 2, 4, 8), tms):
        window_only = ((tm["unfused_bytes"] - tm["pad_bytes_unfused"])
                       / tm["fused_bytes"])
        assert window_only <= t + 1e-9


def test_hbm_traffic_corrected_formulas():
    """Regression pin for the corrected traffic model: fused is pad-free,
    unfused charges one pad_boundary round-trip per sweep, and the
    legacy (padded) fused pipeline is strictly worse than pad-free for
    every paper spec."""
    import math
    spec = PAPER_STENCILS["heat3d"]
    shape, tile, t, item = (64, 64, 64), (4, 16, 128), 3, 4
    tm = engine.hbm_traffic(spec, shape, tile=tile, sweeps=t, itemsize=item)
    halo = spec.halo
    n_tiles = math.prod(-(-n // d) for n, d in zip(shape, tile))
    win = lambda l: math.prod(d + 2 * l * h
                              for d, h in zip(tile, halo)) * item
    out_b = math.prod(tile) * item
    grid_b = math.prod(shape) * item
    pad = lambda l: grid_b + math.prod(n + 2 * l * h
                                       for n, h in zip(shape, halo)) * item
    assert tm["fused_bytes"] == n_tiles * (win(t) + out_b)
    assert tm["unfused_bytes"] == t * (n_tiles * (win(1) + out_b) + pad(1))
    assert tm["pad_bytes_unfused"] == t * pad(1)
    assert tm["legacy_fused_bytes"] == tm["fused_bytes"] + pad(t)
    for s in PAPER_STENCILS.values():
        m = engine.hbm_traffic(s, SHAPES[s.ndim], sweeps=4)
        assert m["fused_bytes"] < m["legacy_fused_bytes"]
        assert m["fused_bytes"] < m["unfused_bytes"]


@pytest.mark.parametrize("sweeps", [1, 2, 4])
def test_casper_engine_pallas_sweeps(sweeps, rng):
    """CasperEngine(sweeps=t) run() equals the unfused ref engine for
    iters both divisible and non-divisible by t."""
    from repro.core import jacobi2d
    g = jnp.asarray(rng.standard_normal((48, 80)), jnp.float32)
    fused = CasperEngine(jacobi2d(), backend="pallas", sweeps=sweeps,
                         tile="auto")
    unfused = CasperEngine(jacobi2d(), backend="ref")
    for iters in (sweeps, 5):
        np.testing.assert_allclose(
            np.asarray(fused.run(g, iters=iters)),
            np.asarray(unfused.run(g, iters=iters)), atol=1e-4)


def test_engine_run_and_lowering_are_profiler_spans(tmp_path, rng):
    """A profile of ``CasperEngine.run`` holds its ``casper.*`` host
    spans: the call, and the first call's plan lowering, autotune and
    verification inside it."""
    import glob
    from jax.profiler import ProfileData
    spec = PAPER_STENCILS["jacobi2d"].with_boundary("periodic")
    g = jnp.asarray(rng.standard_normal((24, 136)), jnp.float32)
    eng = CasperEngine(spec, backend="pallas", sweeps=2, tile="auto")
    with jax.profiler.trace(str(tmp_path)):
        eng.run(g, iters=4).block_until_ready()
        eng.run(g, iters=4).block_until_ready()
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = [ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events
             if ev.name.startswith("casper.")]
    assert sorted(names) == ["casper.autotune", "casper.lower",
                             "casper.run", "casper.run", "casper.verify"]


def test_engine_frozen_after_init(rng):
    """run() caches a jitted loop closing over sweeps/backend/tile, so
    post-init mutation must raise instead of silently running stale
    fused blocks."""
    from repro.core import jacobi2d
    eng = CasperEngine(jacobi2d(), backend="pallas", sweeps=2, tile="auto")
    g = jnp.asarray(rng.standard_normal((32, 40)), jnp.float32)
    eng.run(g, iters=3)
    for attr, val in (("sweeps", 4), ("backend", "ref"), ("tile", None)):
        with pytest.raises(AttributeError):
            setattr(eng, attr, val)
    # still usable after the rejected mutations, with the init options
    np.testing.assert_allclose(
        np.asarray(eng.run(g, iters=3)),
        np.asarray(_chained(PAPER_STENCILS["jacobi2d"], g, 3)), atol=1e-5)


# ---------------------------------------------------------------------------
# Boundary-condition subsystem: mode x rank x sweeps equivalence matrix
# ---------------------------------------------------------------------------
BOUNDARIES = ("zero", "constant(0.75)", "periodic", "reflect")
RANK_SPEC = {1: "jacobi1d", 2: "jacobi2d", 3: "heat3d"}


@pytest.mark.parametrize("rank", [1, 2, 3])
@pytest.mark.parametrize("boundary", BOUNDARIES)
@pytest.mark.parametrize("sweeps", [1, 3])
def test_boundary_modes_fused_match_chained(rank, boundary, sweeps, rng):
    """Fused Pallas sweeps == chained oracle applications for every
    boundary mode at every rank (non-divisible grid shapes)."""
    spec = PAPER_STENCILS[RANK_SPEC[rank]].with_boundary(boundary)
    g = jnp.asarray(rng.standard_normal(SHAPES[rank]), jnp.float32)
    got = engine.stencil_apply(spec, g, sweeps=sweeps)
    want = _chained(spec, g, sweeps)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("boundary", BOUNDARIES)
def test_boundary_modes_f64_bit_identical(boundary, rng):
    """Fused Pallas sweeps are f64 *bit*-identical to chained oracle
    applications under every boundary mode (the acceptance criterion of
    the boundary subsystem): the window is built from bitwise copies of
    interior elements (pad_boundary), ghosts are restored bitwise between
    sweeps, and tap_sum pins the accumulation order."""
    from jax import enable_x64
    spec = PAPER_STENCILS["jacobi2d"].with_boundary(boundary)
    with enable_x64():
        g = jnp.asarray(rng.standard_normal((70, 130)), jnp.float64)
        got = engine.stencil_apply(spec, g, sweeps=3)
        want = jax.jit(lambda x: cref.run_iterations(spec, x, 3))(g)
        assert bool(jnp.all(got == want)), boundary


@pytest.mark.parametrize("boundary", ["periodic", "reflect"])
@pytest.mark.parametrize("name", ["jacobi1d", "blur2d", "star33_3d"])
def test_boundary_grids_smaller_than_halo_window(name, boundary, rng):
    """Deep fused halos on tiny grids force repeated wrap (periodic) and
    repeated fold (reflect) — the t*halo > N corner of the index maps."""
    spec = PAPER_STENCILS[name].with_boundary(boundary)
    g = jnp.asarray(rng.standard_normal(TINY[spec.ndim]), jnp.float32)
    got = engine.stencil_apply(spec, g, sweeps=3)
    want = _chained(spec, g, 3)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


def test_periodic_advection_conserves_mass(rng):
    """advect1d/2d coefficients sum to 1, so under periodic wrap the grid
    total is exactly preserved — the semantic signature of a torus (any
    fill boundary leaks mass at the walls)."""
    from repro.core import advect1d, advect2d
    for spec, shape in ((advect1d(), (640,)), (advect2d(), (48, 80))):
        g = jnp.asarray(rng.random(shape) + 0.5, jnp.float32)  # positive
        out = engine.run_sweeps(spec, g, iters=10, sweeps=5)
        np.testing.assert_allclose(float(jnp.sum(out)), float(jnp.sum(g)),
                                   rtol=1e-4)
        leaky = engine.run_sweeps(spec.with_boundary("zero"), g, iters=10,
                                  sweeps=5)
        assert abs(float(jnp.sum(leaky)) - float(jnp.sum(g))) > 1e-3


def test_boundary_validation_and_parsing():
    from repro.core import jacobi2d, parse_boundary
    assert parse_boundary("constant(2.5)") == ("constant", 2.5)
    assert parse_boundary("zero") == ("zero", 0.0)
    spec = jacobi2d().with_boundary("constant(-1.5)")
    assert spec.boundary_mode == "constant"
    assert spec.boundary_value == -1.5
    for bad in ("mirror", "constant()", "constant(x)", "Periodic"):
        with pytest.raises(ValueError):
            jacobi2d().with_boundary(bad)


def test_casper_engine_boundary_backends_agree(rng):
    """CasperEngine serves the spec's boundary identically on both
    backends, fused and unfused."""
    from repro.core import jacobi2d
    spec = jacobi2d().with_boundary("periodic")
    g = jnp.asarray(rng.standard_normal((48, 80)), jnp.float32)
    fused = CasperEngine(spec, backend="pallas", sweeps=3, tile="auto")
    unfused = CasperEngine(spec, backend="ref")
    np.testing.assert_allclose(
        np.asarray(fused.run(g, iters=5)),
        np.asarray(unfused.run(g, iters=5)), atol=1e-4)


def test_compat_shims_match_engine(rng):
    from repro import kernels
    spec1 = PAPER_STENCILS["7pt1d"]
    spec2 = PAPER_STENCILS["jacobi2d"]
    spec3 = PAPER_STENCILS["heat3d"]
    g1 = jnp.asarray(rng.standard_normal((777,)), jnp.float32)
    g2 = jnp.asarray(rng.standard_normal((70, 130)), jnp.float32)
    g3 = jnp.asarray(rng.standard_normal((9, 20, 150)), jnp.float32)
    for shim, spec, g in [(kernels.stencil1d, spec1, g1),
                          (kernels.stencil2d, spec2, g2),
                          (kernels.stencil3d, spec3, g3)]:
        np.testing.assert_allclose(
            np.asarray(shim(spec, g)),
            np.asarray(_chained(spec, g, 1)), atol=1e-5)


def test_measured_autotune_disk_cache_roundtrip(tmp_path, monkeypatch, rng):
    """``CASPER_TUNE_CACHE`` persistence: the first measured tune
    misses and stores, an identical second call is served from disk
    (hit, no new store), and the cached result round-trips exactly.
    Unsetting the env var disables persistence entirely."""
    spec = PAPER_STENCILS["jacobi1d"]
    g = jnp.asarray(rng.standard_normal((2048,)), jnp.float32)
    monkeypatch.setenv(tune.TUNE_CACHE_ENV, str(tmp_path))
    tune.TUNE_DISK_CACHE.reset()
    first = tune.autotune_measured(spec, g, sweeps=1, top_k=2, reps=1)
    assert tune.TUNE_DISK_CACHE.as_dict() == {"hits": 0, "misses": 1,
                                              "stores": 1}
    assert first.measured
    again = tune.autotune_measured(spec, g, sweeps=1, top_k=2, reps=1)
    assert tune.TUNE_DISK_CACHE.as_dict() == {"hits": 1, "misses": 1,
                                              "stores": 1}
    assert again.tile == first.tile
    assert again.table == first.table
    # a different measurement configuration never aliases the first
    tune.autotune_measured(spec, g, sweeps=2, top_k=2, reps=1)
    assert tune.TUNE_DISK_CACHE.as_dict() == {"hits": 1, "misses": 2,
                                              "stores": 2}
    monkeypatch.delenv(tune.TUNE_CACHE_ENV)
    counters = tune.TUNE_DISK_CACHE.as_dict()
    tune.autotune_measured(spec, g, sweeps=1, top_k=2, reps=1)
    assert tune.TUNE_DISK_CACHE.as_dict() == counters   # untouched

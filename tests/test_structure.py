"""Structure-specialized compute core: classification, exact
factorization, the structure x boundary x rank x sweeps equivalence
matrix (f64 bitwise), the pad-free fused path, and the jaxpr guard
against silent de-specialization."""
import math

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import (PAPER_STENCILS, CasperEngine, StencilSpec, assemble,
                        factor_taps, plan_streams)
from repro.core import plan as _plan
from repro.core import ref as cref
from repro.kernels import engine

SHAPES = {1: (1000,), 2: (70, 130), 3: (9, 20, 150)}

# A genuinely dense spec: coupled taps that do not factor (corner coeff
# breaks the outer-product identity).
DENSE2D = StencilSpec("dense2d", 2, (
    ((0, 0), 0.5), ((-1, 0), 0.125), ((1, 0), 0.125),
    ((0, -1), 0.0625), ((0, 1), 0.0625), ((1, 1), 0.11),
))


# ---------------------------------------------------------------------------
# Classification + factorization
# ---------------------------------------------------------------------------
def test_classification_of_paper_stencils():
    expect = {"jacobi1d": "star", "7pt1d": "star", "jacobi2d": "star",
              "heat3d": "star", "blur2d": "separable",
              "star33_3d": "separable"}
    for name, structure in expect.items():
        spec = PAPER_STENCILS[name]
        assert spec.structure == structure, name
        fz = factor_taps(spec)
        assert fz.structure == structure
        if structure == "star":
            assert fz.tap_ops == spec.n_taps
        else:
            assert fz.tap_ops < spec.n_taps, name
    # headline factored op counts: blur2d 5x5 -> 5+5, star33 -> 9 core + 6
    assert factor_taps(PAPER_STENCILS["blur2d"]).tap_ops == 10
    assert factor_taps(PAPER_STENCILS["star33_3d"]).tap_ops == 15
    assert DENSE2D.structure == "dense"
    assert factor_taps(DENSE2D).terms is None


def test_structure_forcing_and_validation():
    spec = PAPER_STENCILS["blur2d"]
    dense = spec.with_structure("dense")
    assert dense.structure == "dense"
    assert factor_taps(dense).terms is None
    assert factor_taps(dense).tap_ops == spec.n_taps
    # re-deriving auto gets the classification back
    assert dense.with_structure("auto").structure == "separable"
    # asserting the true class is allowed; a wrong class raises
    assert spec.with_structure("separable").structure == "separable"
    with pytest.raises(ValueError):
        spec.with_structure("star")
    with pytest.raises(ValueError):
        spec.with_structure("boxy")
    # forced-dense participates in equality/cache keys
    assert dense != spec


def test_factorization_reconstructs_dense_taps():
    """factor_taps unit-tested against the dense form: expanding the
    terms (outer products of the 1-D factors) reproduces every tap
    coefficient to float rounding, and misses none."""
    for name in ("jacobi1d", "jacobi2d", "blur2d", "heat3d", "star33_3d"):
        spec = PAPER_STENCILS[name]
        fz = factor_taps(spec)
        got: dict = {}
        for term in fz.terms:
            expanded = {(0,) * spec.ndim: 1.0}
            for f in term.factors:
                nxt = {}
                for off, c in expanded.items():
                    for o, fc in zip(f.offsets, f.coeffs):
                        p = list(off)
                        p[f.axis] = o
                        nxt[tuple(p)] = c * fc
                expanded = nxt
            for off, c in expanded.items():
                got[off] = got.get(off, 0.0) + c
        want = dict(spec.taps)
        assert set(got) == set(want), name
        for off, c in want.items():
            assert got[off] == pytest.approx(c, rel=1e-12), (name, off)


def test_star33_factorization_is_float_exact():
    """star33_3d's separable core factors with ratio vectors [1/2,1,1/2]
    (power-of-two scalings are exact in floats), so the factored
    coefficients reproduce the dense taps *bitwise*."""
    fz = factor_taps(PAPER_STENCILS["star33_3d"])
    core = fz.terms[0]
    assert [f.coeffs for f in core.factors][1:] == [(0.5, 1.0, 0.5)] * 2
    taps = dict(PAPER_STENCILS["star33_3d"].taps)
    fz0 = core.factors[0]
    for i, o in enumerate(fz0.offsets):
        assert fz0.coeffs[i] == taps[(o, 0, 0)]


def test_random_coupled_specs_fall_back_dense(rng):
    """Random coupled tap sets are (almost surely) not separable: the
    classifier must prove the factorization, not guess it."""
    for seed in range(5):
        r = np.random.default_rng(seed)
        taps = tuple((tuple(int(x) for x in off), float(r.uniform(-1, 1)))
                     for off in ((0, 0), (1, 1), (-1, 1), (1, -1)))
        spec = StencilSpec("randbox", 2, taps)
        assert spec.structure == "dense"
        g = r.standard_normal((12, 13))
        np.testing.assert_allclose(
            cref.apply_stencil_numpy(spec, g),
            cref.apply_stencil_loops(spec, g), atol=1e-12)


# ---------------------------------------------------------------------------
# Equivalence matrix: structure x boundary x rank x sweeps, f64 bitwise
# ---------------------------------------------------------------------------
MATRIX_SPECS = ("jacobi1d", "jacobi2d", "heat3d", "blur2d", "star33_3d")
BOUNDARIES = ("zero", "constant(0.75)", "periodic", "reflect")
STRATEGIES = ("padded-window", "pad-free")

# (grid, tile) per rank for the two kernel strategies.  The padded grids
# are a multiple of no candidate tile, so they take the padded-window
# fallback under the autotuned tile.  The pad-free grids are a multiple
# of a granule-aligned tile at least as deep as the aligned fetch halo
# of the deepest case (blur2d / star33_3d at sweeps=3), so the kernel
# DMAs every window, wrapped ghost slabs included, from the bare grid.
# Where the fetch halo equals the tile, the axis has more than two tiles,
# so a tile's two neighbours (and its two ghost slabs) are distinct.
STRATEGY_CASES = {
    "padded-window": {1: ((260,), None), 2: ((33, 47), None),
                      3: ((9, 13, 21), None)},
    "pad-free": {1: ((4096,), (1024,)), 2: ((32, 384), (16, 128)),
                 3: ((16, 24, 384), (8, 8, 128))},
}


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("name", MATRIX_SPECS)
@pytest.mark.parametrize("boundary", BOUNDARIES)
@pytest.mark.parametrize("sweeps", [1, 3])
def test_structure_equivalence_matrix_f64_bitwise(name, boundary, sweeps,
                                                  strategy, rng):
    """The fused Pallas engine, the jnp oracle chain and the numpy
    oracle chain agree *bitwise* in f64 for every structure
    class, boundary mode, rank, sweep count and kernel strategy — they
    share the factored compute core and its pinned accumulation order —
    and all stay within float tolerance of the forced-dense oracle."""
    from jax import enable_x64
    spec = PAPER_STENCILS[name].with_boundary(boundary)
    shape, tile = STRATEGY_CASES[strategy][spec.ndim]
    with enable_x64():
        g = jnp.asarray(rng.standard_normal(shape), jnp.float64)
        assert _plan.ghost_strategy_for(
            spec, g.shape, g.dtype.itemsize, sweeps, tile) \
            == strategy
        got = engine.stencil_apply(spec, g, tile=tile, sweeps=sweeps)
        want = jax.jit(lambda x: cref.run_iterations(spec, x, sweeps))(g)
        assert bool(jnp.all(got == want)), (name, boundary)
        gn = np.asarray(g)
        for _ in range(sweeps):
            gn = cref.apply_stencil_numpy(spec, gn)
        np.testing.assert_array_equal(np.asarray(got), gn)
        dn = np.asarray(g)
        dense = spec.with_structure("dense")
        for _ in range(sweeps):
            dn = cref.apply_stencil_numpy(dense, dn)
        np.testing.assert_allclose(gn, dn, atol=1e-12)


def test_dense_spec_through_engine(rng):
    """The dense fallback class runs the per-tap path end to end."""
    g = jnp.asarray(rng.standard_normal((33, 47)), jnp.float32)
    got = engine.stencil_apply(DENSE2D, g, sweeps=2)
    want = jax.jit(lambda x: cref.run_iterations(DENSE2D, x, 2))(g)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)


@pytest.mark.parametrize("strategy", STRATEGIES)
@pytest.mark.parametrize("boundary", BOUNDARIES)
def test_dense_spec_f64_bitwise(boundary, strategy, rng):
    """The dense per-tap path is f64 bit-identical to the jnp and numpy
    oracle chains under both kernel strategies and every boundary."""
    from jax import enable_x64
    spec = DENSE2D.with_boundary(boundary)
    shape, tile = STRATEGY_CASES[strategy][2]
    with enable_x64():
        g = jnp.asarray(rng.standard_normal(shape), jnp.float64)
        assert _plan.ghost_strategy_for(
            spec, g.shape, g.dtype.itemsize, 2, tile) \
            == strategy
        got = engine.stencil_apply(spec, g, tile=tile, sweeps=2)
        want = jax.jit(lambda x: cref.run_iterations(spec, x, 2))(g)
        assert bool(jnp.all(got == want)), boundary
        gn = np.asarray(g)
        for _ in range(2):
            gn = cref.apply_stencil_numpy(spec, gn)
        np.testing.assert_array_equal(np.asarray(got), gn)


@pytest.mark.parametrize("boundary", BOUNDARIES)
def test_padfree_matches_padded_window_path(boundary, rng):
    """The pad-free kernel's wrapped fetch plus in-kernel ghost
    restoration is bitwise what pad_boundary would have produced: the
    pad-free stencil_sweep equals the padded stencil_window_sweep
    exactly in f64."""
    from jax import enable_x64
    spec = PAPER_STENCILS["jacobi2d"].with_boundary(boundary)
    with enable_x64():
        g = jnp.asarray(rng.standard_normal((64, 256)), jnp.float64)
        sweeps, tile = 3, (16, 128)
        assert _plan.ghost_strategy_for(
            spec, g.shape, g.dtype.itemsize, sweeps, tile) \
            == "pad-free"
        padfree = engine.stencil_sweep(spec, g, tile=tile, sweeps=sweeps)
        wide = tuple(sweeps * h for h in spec.halo)
        window = cref.pad_boundary(g, wide, spec.boundary_mode,
                                   spec.boundary_value)
        padded = engine.stencil_window_sweep(
            spec, window, g.shape, (0, 0), g.shape, tile=tile,
            sweeps=sweeps)
        assert bool(jnp.all(padfree == padded)), boundary


def test_periodic_strategy_independent_of_grid_size(rng):
    """Periodic grids take the same windowed pad-free fetch as every
    other mode, at any size (the ghost slabs wrap around the grid edge
    by DMA); a grid that is not a multiple of the tile falls back to
    the wrap-padded window path — same bits either way."""
    from jax import enable_x64
    spec = PAPER_STENCILS["jacobi2d"].with_boundary("periodic")
    big = jax.ShapeDtypeStruct((4096, 4096), jnp.float32)
    assert _plan.ghost_strategy_for(
        spec, big.shape, big.dtype.itemsize, 4, (32, 256)) \
        == "pad-free"
    with enable_x64():
        g = jnp.asarray(rng.standard_normal((70, 130)), jnp.float64)
        assert _plan.ghost_strategy_for(
            spec, g.shape, g.dtype.itemsize, 3, None) \
            == "padded-window"
        got = engine.stencil_sweep(spec, g, sweeps=3)
        want = jax.jit(lambda x: cref.run_iterations(spec, x, 3))(g)
        assert bool(jnp.all(got == want))


# ---------------------------------------------------------------------------
# jaxpr guard: the specialized paths must stay specialized.  The one-off
# counter that used to live here is now the real de-specialization pass
# in repro.analysis (jaxpr_lint); this test pins the tightest per-call
# bounds on the oracle, the pass bounds every lowered plan's executor.
# ---------------------------------------------------------------------------
from repro.analysis import count_primitive as _count_primitive  # noqa: E402
from repro.analysis import count_tap_windows as _count_taps  # noqa: E402


@pytest.mark.parametrize("name", MATRIX_SPECS)
def test_jaxpr_slice_count_guard(name, rng):
    """One stencil application must emit at most ``tap_ops`` window
    slices — ``sum(2r_d)+1`` for a star spec, the factored pass total
    for a separable spec (10 for blur2d, 15 for star33_3d) — never the
    dense tap count of a de-specialized path."""
    spec = PAPER_STENCILS[name]
    g = jnp.zeros(SHAPES[spec.ndim], jnp.float32)
    jaxpr = jax.make_jaxpr(lambda x: cref.apply_stencil(spec, x))(g).jaxpr
    n_slices = _count_taps(jaxpr)
    fz = factor_taps(spec)
    assert n_slices <= fz.tap_ops, (name, n_slices, fz.tap_ops)
    if spec.structure == "star":
        bound = sum(2 * h for h in spec.halo) + 1
        assert n_slices <= bound, (name, n_slices, bound)
    else:
        assert n_slices < spec.n_taps, (name, n_slices)
    # add chain stays O(tap_ops): per-pass accumulates + the term sum
    n_adds = _count_primitive(jaxpr, "add")
    n_terms = len(fz.terms)
    assert n_adds <= fz.tap_ops + n_terms, (name, n_adds)


# ---------------------------------------------------------------------------
# Plan/ISA recording + perf-model structure awareness
# ---------------------------------------------------------------------------
def test_stream_plan_and_program_record_structure():
    for name, spec in PAPER_STENCILS.items():
        plan = plan_streams(spec)
        prog = assemble(spec)
        assert plan.structure == spec.structure
        assert plan.structured_ops == factor_taps(spec).tap_ops
        assert prog.structure == spec.structure
        assert prog.structured_n_instrs == factor_taps(spec).tap_ops
    prog = assemble(PAPER_STENCILS["star33_3d"])
    n = 10_000
    dense = prog.dynamic_instruction_count(n)
    struct = prog.dynamic_instruction_count(n, structured=True)
    assert dense["per_spu"] == -(-(-(-n // 16)) // 8) * 33
    assert struct["per_spu"] == -(-(-(-n // 16)) // 8) * 15
    assert struct["total"] < dense["total"]


def test_tile_cost_structure_aware():
    """The autotuner cost model charges the factored flop count: the
    compute term of a forced-dense separable spec is >= the structured
    one at every candidate tile (traffic is identical), and the cache
    keys differ so both coexist."""
    from repro.core import perfmodel as pm
    from repro.kernels import tune
    spec = PAPER_STENCILS["star33_3d"]
    dense = spec.with_structure("dense")
    shape = (256, 256, 64)
    for tile in tune.candidate_tiles(3, shape):
        cs = pm.pallas_tile_cost(spec, shape, tile, sweeps=4)
        cd = pm.pallas_tile_cost(dense, shape, tile, sweeps=4)
        assert cd >= cs or math.isinf(cs)
    rs = tune.autotune(spec, shape, sweeps=4)
    rd = tune.autotune(dense, shape, sweeps=4)
    assert rs.cost_s <= rd.cost_s
    assert spec.structured_flops_per_point() < spec.flops_per_point()
    assert dense.structured_flops_per_point() == dense.flops_per_point()


# ---------------------------------------------------------------------------
# interpret=None auto-detection
# ---------------------------------------------------------------------------
def test_interpret_auto_detection(rng, monkeypatch):
    assert engine.resolve_interpret(None) == (jax.default_backend() == "cpu")
    assert engine.resolve_interpret(True) is True
    assert engine.resolve_interpret(False) is False
    # the CPU host interprets; the chip compiles
    from repro.core import plan as _plan
    with monkeypatch.context() as mp:
        mp.setattr(_plan.jax, "default_backend", lambda: "tpu")
        assert _plan.resolve_interpret(None) is False
        assert _plan.resolve_interpret(True) is True   # explicit
    # default (None) paths run fine on CPU without passing the flag
    g = jnp.asarray(rng.standard_normal((48, 64)), jnp.float32)
    spec = PAPER_STENCILS["jacobi2d"]
    np.testing.assert_allclose(
        np.asarray(engine.run_sweeps(spec, g, iters=3, sweeps=2)),
        np.asarray(jax.jit(lambda x: cref.run_iterations(spec, x, 3))(g)),
        atol=1e-5)
    eng = CasperEngine(spec, backend="pallas", sweeps=2)
    assert eng.interpret is True     # resolved at init on a CPU backend


def test_distributed_structure_parity(rng):
    """The distributed shard-local path dispatches the same factored
    core: separable spec, f64 bitwise vs the single-device oracle."""
    import os
    import subprocess
    import sys
    import textwrap
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=4 "
                                   + os.environ.get("XLA_FLAGS", ""))
        import numpy as np
        import jax, jax.numpy as jnp
        jax.config.update("jax_enable_x64", True)
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import blur2d, distributed_stencil_fn
        from repro.core import ref as cref
        spec = blur2d().with_boundary("reflect")
        mesh = jax.make_mesh((4,), ("sx",))
        g = jnp.asarray(np.random.default_rng(0).standard_normal((32, 48)))
        fn = distributed_stencil_fn(spec, mesh, ("sx", None), iters=4,
                                    sweeps=2)
        gs = jax.device_put(g, NamedSharding(mesh, P("sx", None)))
        want = cref.run_iterations(spec, g, 4)
        assert bool(jnp.all(fn(gs) == want)), "distributed != oracle"
        print("DIST_OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "src")
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr
    assert "DIST_OK" in proc.stdout

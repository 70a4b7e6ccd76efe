"""Differential property-test harness: random specs and random stage
chains, every executor vs the chained f64 oracle.

Case generation is driven by ``hypothesis`` when installed and by the
deterministic fixed-seed sweep of ``tests/_hypothesis_compat.py``
otherwise; either way a case is fully determined by a single integer
seed (plus a couple of coarse axes), so any failure is reproducible by
seed — and previously-failing seeds are pinned forever in
``REGRESSION_CORPUS``.

Contract per case, against the **eager chained per-stage oracle**
(each stage one ``ref.apply_stencil`` call — no cross-stage compiler
involvement, the definitional ground truth):

* ``ref`` and ``pallas`` fused plans executed block-by-block
  (``plan.execute``), and the single-device distributed path, are
  **f64 bit-identical**;
* the ``run_plan`` scan composition matches to ``atol=1e-12``: rolling
  several chain applications into one XLA computation licenses
  cross-stage FMA contraction on arbitrary tap sets (the paper
  stencils' factored cores pin their order, arbitrary fuzzed taps
  cannot), so the scan path is held to the same reassociation bound as
  the VM — found by this very harness, seed 29 of the corpus;
* the SPU VM (dense tap order) matches to the repo-wide reassociation
  bound, ``atol=1e-12``;
* f32 grids match the f64 oracle to 1e-4 through every executor.

The unmarked tests are the tier-1 fast lane (a handful of cases); the
``fuzz``-marked deep sweeps run in the scheduled CI job with
``CASPER_FUZZ_EXAMPLES`` cases *each* (>= 200 total across the two deep
tests at the default 100).
"""
import contextlib
import os
import zlib

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

from tests._hypothesis_compat import given, settings, st

DEEP_EXAMPLES = int(os.environ.get("CASPER_FUZZ_EXAMPLES", "100"))
FAST_EXAMPLES = 5

BOUNDARIES = ("zero", "constant(0.5)", "periodic", "reflect")
NONPERIODIC = ("zero", "constant(0.5)", "reflect")
SHAPES = {1: (23,), 2: (11, 17), 3: (5, 7, 9)}


# ---------------------------------------------------------------------------
# Seed -> case
# ---------------------------------------------------------------------------
def random_spec(rng: np.random.Generator, ndim: int, boundary: str,
                name: str) -> StencilSpec:
    """A random spec: random radius (1-2), random tap set inside the
    radius box (center always present, so specs are well-conditioned),
    random coefficients, randomly forced-dense structure."""
    radius = int(rng.integers(1, 3))
    n_extra = int(rng.integers(1, 5))
    offs = {(0,) * ndim}
    for _ in range(n_extra):
        offs.add(tuple(int(o) for o in
                       rng.integers(-radius, radius + 1, size=ndim)))
    taps = tuple((off, float(np.round(rng.uniform(-1.0, 1.0), 4)))
                 for off in sorted(offs))
    structure = "dense" if rng.random() < 0.25 else "auto"
    return StencilSpec(name, ndim, taps, boundary=boundary,
                       structure=structure)


def random_pipeline(seed: int, ndim: int, periodic: bool,
                    n_stages: int) -> StencilPipeline:
    """A random fusable chain: all stages periodic, or each stage a
    random non-periodic boundary (the two fusable families)."""
    rng = np.random.default_rng(seed)
    stages = tuple(
        random_spec(rng, ndim,
                    "periodic" if periodic
                    else NONPERIODIC[int(rng.integers(len(NONPERIODIC)))],
                    f"fz{seed}_s{k}")
        for k in range(n_stages))
    return StencilPipeline(f"fuzz_pipe_{seed}", stages)


@contextlib.contextmanager
def _forced_budget(n_bytes: int):
    """Scope ``CASPER_SLAB_BUDGET`` for one slabbed lowering+run."""
    from repro.core import perfmodel as _pm
    old = os.environ.get(_pm.SLAB_BUDGET_ENV)
    os.environ[_pm.SLAB_BUDGET_ENV] = str(int(n_bytes))
    try:
        yield
    finally:
        if old is None:
            os.environ.pop(_pm.SLAB_BUDGET_ENV, None)
        else:
            os.environ[_pm.SLAB_BUDGET_ENV] = old


def _slab_budget_for(name: str, nbytes: int) -> int:
    """A deterministic per-case budget strictly below the grid bytes, so
    every fuzzed case also streams: the divisor (2-5) comes from a
    stable hash of the case name, never from Python's salted hash."""
    return max(1, nbytes // (2 + zlib.crc32(name.encode()) % 4))


def _assert_verified(plan) -> None:
    """Every fuzzed plan rides through the static verifier for free:
    ``lower()`` already verified it on the cache miss (warn mode), so
    just assert the recorded report is error-free."""
    from repro import analysis
    report = analysis.report_for(plan) or analysis.verify_plan(plan)
    assert report.ok, report.pretty()


def check_executors(pipe: StencilPipeline, sweeps: int,
                    f32: bool = False) -> None:
    """The differential assertion: all four executors vs the eager
    chained per-stage f64 oracle, over ``iters = 2 * sweeps``
    applications (two fused blocks)."""
    shape = SHAPES[pipe.ndim]
    iters = 2 * sweeps
    with enable_x64():
        g = jnp.asarray(
            np.random.default_rng(0).standard_normal(shape))
        want = g
        for _ in range(iters):
            for s in pipe.stages:
                want = rc.apply_stencil(s, want)
        want = np.asarray(want)

        if f32:
            g32 = g.astype(jnp.float32)
            for backend in ("ref", "pallas"):
                plan = _plan.lower(pipe, shape, jnp.float32,
                                   backend=backend, sweeps=sweeps)
                _assert_verified(plan)
                got = np.asarray(_plan.run_plan(plan, g32, iters))
                np.testing.assert_allclose(got, want, atol=1e-4,
                                           err_msg=f"f32 {backend}")
                with _forced_budget(_slab_budget_for(pipe.name,
                                                     g32.nbytes)):
                    slabbed = _plan.lower(pipe, shape, jnp.float32,
                                          backend=backend, sweeps=sweeps)
                    _assert_verified(slabbed)
                    streamed = np.asarray(
                        _plan.run_plan(slabbed, np.asarray(g32), iters))
                np.testing.assert_allclose(
                    streamed, want, atol=1e-4,
                    err_msg=f"f32 {backend} slab-streamed")
            return

        for backend in ("ref", "pallas"):
            plan = _plan.lower(pipe, shape, g.dtype, backend=backend,
                               sweeps=sweeps)
            _assert_verified(plan)
            got = g
            for _ in range(iters // sweeps):        # eager fused blocks
                got = _plan.execute(plan, got)
            np.testing.assert_array_equal(np.asarray(got), want,
                                          err_msg=backend)
            # the scan composition: reassociation bound only (see module
            # docstring)
            scanned = np.asarray(_plan.run_plan(plan, g, iters))
            np.testing.assert_allclose(scanned, want, atol=1e-12,
                                       err_msg=f"{backend} run_plan")
            # the slab-streamed composition: a budget below the grid
            # bytes forces the same case onto "stream-from-host"; each
            # slab runs the same windowed executors as the distributed
            # path, so bit-identity to the oracle must survive slabbing
            with _forced_budget(_slab_budget_for(pipe.name, g.nbytes)):
                slabbed = _plan.lower(pipe, shape, g.dtype,
                                      backend=backend, sweeps=sweeps)
                _assert_verified(slabbed)
                assert slabbed.ghost_strategy == "stream-from-host"
                streamed = np.asarray(
                    _plan.run_plan(slabbed, np.asarray(g), iters))
            np.testing.assert_array_equal(
                streamed, want, err_msg=f"{backend} slab-streamed")

        mesh = Mesh(np.array(jax.devices()[:1]), ("sx",))
        axes = ("sx",) + (None,) * (pipe.ndim - 1)
        fn = rc.distributed_stencil_fn(pipe, mesh, axes, iters=iters,
                                       sweeps=sweeps)
        np.testing.assert_array_equal(np.asarray(fn(g)), want,
                                      err_msg="distributed")

        plan = _plan.lower(pipe, shape, g.dtype, backend="vm")
        _assert_verified(plan)
        got, _ = _vm.execute_plan(plan, np.asarray(g), iters=iters)
        np.testing.assert_allclose(got, want, atol=1e-12, err_msg="vm")


def run_case(seed: int, ndim: int, periodic: bool, n_stages: int,
             sweeps: int, f32: bool = False) -> None:
    pipe = random_pipeline(seed, ndim, periodic, n_stages)
    check_executors(pipe, sweeps=sweeps, f32=f32)


# ---------------------------------------------------------------------------
# Seed-pinned regression corpus — always in the tier-1 fast lane.
# Each entry is (seed, ndim, periodic, n_stages, sweeps): cases that
# exercised tricky paths during development (deep reflect mirrors,
# all-periodic wrap invariant under sweeps>1, radius-2 + forced-dense
# stages, rank-1 and rank-3 chains).  Append, never remove.
# ---------------------------------------------------------------------------
REGRESSION_CORPUS = (
    (1, 2, False, 2, 1),
    (7, 2, False, 3, 2),
    (13, 2, True, 2, 2),
    (29, 1, False, 4, 1),
    (31, 1, True, 3, 2),
    (42, 3, False, 2, 1),
    (57, 3, True, 2, 1),
    (101, 2, False, 4, 1),
    # slab-streamed coverage (every corpus entry now also runs the
    # forced-budget leg): rank-3 sweeps=2 makes the overlap deeper than
    # a single slab under the hashed budget; rank-1 periodic wraps the
    # slab window gather around both grid ends
    (163, 3, False, 2, 2),
    (211, 1, True, 2, 2),
)


@pytest.mark.parametrize("case", REGRESSION_CORPUS,
                         ids=lambda c: f"seed{c[0]}_nd{c[1]}"
                                       f"{'_per' if c[2] else ''}"
                                       f"_k{c[3]}_t{c[4]}")
def test_regression_corpus(case):
    run_case(*case)


def test_regression_corpus_f32():
    seed, ndim, periodic, n_stages, sweeps = REGRESSION_CORPUS[1]
    run_case(seed, ndim, periodic, n_stages, sweeps, f32=True)


# ---------------------------------------------------------------------------
# Single-spec differential fuzz (a 1-stage pipeline IS the spec, so the
# same harness covers the spec axes: rank x taps x boundary x structure
# x dtype x sweeps)
# ---------------------------------------------------------------------------
@settings(max_examples=FAST_EXAMPLES, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       ndim=st.sampled_from((1, 2)),
       boundary=st.sampled_from(BOUNDARIES),
       sweeps=st.sampled_from((1, 2)),
       f32=st.booleans())
def test_fuzz_single_specs(seed, ndim, boundary, sweeps, f32):
    rng = np.random.default_rng(seed)
    spec = random_spec(rng, ndim, boundary, f"fz{seed}")
    check_executors(StencilPipeline(f"fz{seed}_p", (spec,)),
                    sweeps=sweeps, f32=f32)


@settings(max_examples=FAST_EXAMPLES, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       ndim=st.sampled_from((1, 2)),
       periodic=st.booleans(),
       n_stages=st.integers(2, 4),
       sweeps=st.sampled_from((1, 2)))
def test_fuzz_pipelines(seed, ndim, periodic, n_stages, sweeps):
    run_case(seed, ndim, periodic, n_stages, sweeps)


@settings(max_examples=FAST_EXAMPLES, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       n_stages=st.integers(2, 3))
def test_fuzz_unfusable_staged_fallback(seed, n_stages):
    # mixed periodic/non-periodic: must lower staged and still match
    rng = np.random.default_rng(seed)
    stages = [random_spec(rng, 2, "periodic", f"fz{seed}_p0")]
    stages += [random_spec(rng, 2,
                           NONPERIODIC[int(rng.integers(len(NONPERIODIC)))],
                           f"fz{seed}_s{k}")
               for k in range(1, n_stages)]
    pipe = StencilPipeline(f"fuzz_mixed_{seed}", tuple(stages))
    assert not pipe.fusable
    with enable_x64():
        g = jnp.asarray(np.random.default_rng(0).standard_normal((11, 17)))
        want = g
        for _ in range(2):
            for s in pipe.stages:
                want = rc.apply_stencil(s, want)
        want = np.asarray(want)
        for backend in ("ref", "pallas"):
            plan = _plan.lower(pipe, g.shape, g.dtype, backend=backend)
            _assert_verified(plan)
            assert not plan.fused
            got = g
            for _ in range(2):
                got = _plan.execute(plan, got)
            np.testing.assert_array_equal(np.asarray(got), want,
                                          err_msg=backend)


# ---------------------------------------------------------------------------
# Deep sweeps: the scheduled CI fuzz lane (pytest -m fuzz)
# ---------------------------------------------------------------------------
@pytest.mark.fuzz
@settings(max_examples=DEEP_EXAMPLES, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       ndim=st.sampled_from((1, 2, 3)),
       boundary=st.sampled_from(BOUNDARIES),
       sweeps=st.sampled_from((1, 2, 3)),
       f32=st.booleans())
def test_fuzz_single_specs_deep(seed, ndim, boundary, sweeps, f32):
    rng = np.random.default_rng(seed)
    spec = random_spec(rng, ndim, boundary, f"fz{seed}")
    check_executors(StencilPipeline(f"fz{seed}_p", (spec,)),
                    sweeps=sweeps, f32=f32)


@pytest.mark.fuzz
@settings(max_examples=DEEP_EXAMPLES, deadline=None)
@given(seed=st.integers(0, 2**31 - 1),
       ndim=st.sampled_from((1, 2, 3)),
       periodic=st.booleans(),
       n_stages=st.integers(2, 4),
       sweeps=st.sampled_from((1, 2)))
def test_fuzz_pipelines_deep(seed, ndim, periodic, n_stages, sweeps):
    run_case(seed, ndim, periodic, n_stages, sweeps)

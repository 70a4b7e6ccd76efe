"""The plan verifier + jaxpr/HLO lint (repro.analysis).

Effectiveness is proven by mutation testing: every seeded corruption
class (wrong halo depth, illegal ghost strategy, oversized tile,
dropped factorization, broken decomposition, wrong exchange strategy,
non-canonical dtype, de-specialized compute, narrowed dtype, forced
HBM round-trip) must be flagged, and the clean paper matrix must
produce zero error/warning findings (zero false positives).  Wiring is
pinned too: every ``plan.lower()`` cache miss verifies exactly once, a
second identical lower re-runs zero analyses, strict mode raises
``PlanVerificationError`` and keeps the bad plan out of the cache.
"""
import dataclasses
import math
import warnings

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro import analysis
from repro.core import perfmodel as pm
from repro.core import plan as _plan
from repro.core.engine import CasperEngine
from repro.core.stencil import (PAPER_PIPELINES, PAPER_STENCILS,
                                factor_taps)
from repro.analysis import jaxpr_lint, verify

SHAPES = {1: (512,), 2: (64, 128), 3: (8, 16, 128)}
BOUNDARIES = ("zero", "constant(0.5)", "periodic", "reflect")


def lower(spec, backend="pallas", sweeps=2, dtype=jnp.float64, **kw):
    return _plan.lower(spec, SHAPES[spec.ndim], dtype, backend=backend,
                       sweeps=sweeps, **kw)


# ---------------------------------------------------------------------------
# Clean matrix: zero false positives
# ---------------------------------------------------------------------------
def test_clean_matrix_zero_findings():
    """Every paper spec x boundary x structure x backend lowers to a
    plan the layer-1 verifier passes with zero errors AND zero
    warnings — the zero-false-positive baseline (the full matrix with
    layer 2 runs in CI via tools/casper_lint.py)."""
    for spec in PAPER_STENCILS.values():
        for boundary in BOUNDARIES:
            for structure in ("auto", "dense"):
                s = spec.with_boundary(boundary).with_structure(structure)
                for backend in ("ref", "pallas", "vm"):
                    p = lower(s, backend=backend)
                    rep = analysis.report_for(p) or analysis.verify_plan(p)
                    assert not rep.errors, rep.pretty()
                    assert not rep.warnings, rep.pretty()


def test_clean_pipelines_zero_findings():
    for pipe in PAPER_PIPELINES.values():
        for backend in ("ref", "pallas", "vm"):
            p = lower(pipe, backend=backend)
            rep = analysis.report_for(p) or analysis.verify_plan(p)
            assert not rep.errors, rep.pretty()
            assert not rep.warnings, rep.pretty()


# ---------------------------------------------------------------------------
# Mutation testing: seeded corruptions, each class flagged
# ---------------------------------------------------------------------------
def _clean_plan():
    return lower(PAPER_STENCILS["blur2d"])


def _errors_of(mutant):
    rep = analysis.verify_plan(mutant)
    return {f.check for f in rep.errors}


def test_mutation_wrong_halo_depth():
    clean = _clean_plan()                 # 5x5 blur: halo (2, 2), sweeps=2
    assert clean.deep_halo == tuple(clean.sweeps * h for h in clean.halo)
    mut = dataclasses.replace(
        clean, deep_halo=tuple(h + 1 for h in clean.deep_halo))
    assert "halo-arithmetic" in _errors_of(mut)
    mut = dataclasses.replace(
        clean, halo=tuple(h + 1 for h in clean.halo))
    assert "halo-arithmetic" in _errors_of(mut)


def test_mutation_illegal_ghost_strategy():
    clean = _clean_plan()
    assert "ghost-strategy" in _errors_of(
        dataclasses.replace(clean, ghost_strategy="pad"))
    assert "ghost-strategy" in _errors_of(
        dataclasses.replace(clean, ghost_strategy="bogus"))


def test_mutation_oversized_tile():
    clean = _clean_plan()
    mut = dataclasses.replace(clean, tile=(2048, 2048))
    assert "vmem-budget" in _errors_of(mut)


def test_mutation_dropped_factorization():
    clean = _clean_plan()
    dense_fz = factor_taps(clean.spec.with_structure("dense"))
    assert "factorization" in _errors_of(
        dataclasses.replace(clean, factorization=dense_fz))
    assert "factorization" in _errors_of(
        dataclasses.replace(clean, factorization=None))


def test_mutation_broken_decompose():
    clean = _clean_plan()
    assert "decompose" in _errors_of(
        dataclasses.replace(clean, sweeps=0))


def test_mutation_wrong_exchange_strategy():
    spec = PAPER_STENCILS["jacobi2d"].with_boundary("periodic")
    mesh = Mesh(np.array(jax.devices()[:1]), ("sx",))
    p = lower(spec, mesh=mesh, grid_axes=("sx", None))
    assert analysis.verify_plan(p).ok
    mut = dataclasses.replace(p, exchange=("zero-fill", None))
    assert "distributed" in _errors_of(mut)
    mut = dataclasses.replace(p, shard_shape=(32, 128))
    assert "distributed" in _errors_of(mut)


def test_mutation_noncanonical_dtype():
    clean = _clean_plan()
    assert "plan-fields" in _errors_of(
        dataclasses.replace(clean, dtype="double"))


def test_mutation_fused_flag():
    clean = _clean_plan()
    # a single-spec plan can never be staged
    mut = dataclasses.replace(clean, fused=False,
                              ghost_strategy="staged", tile=None)
    assert "fusability" in _errors_of(mut)


def test_mutation_despecialized_compute(monkeypatch):
    """Silently dropping the factored compute path (compute_terms ->
    None) makes the traced executor walk the dense tap chain: the
    de-specialization lint must catch the extra slices."""
    from repro.core.stencil import Factorization
    plan = lower(PAPER_STENCILS["blur2d"], backend="ref")
    assert not jaxpr_lint.lint_despecialization(plan)
    monkeypatch.setattr(Factorization, "compute_terms",
                        property(lambda self: None))
    findings = jaxpr_lint.lint_despecialization(plan)
    assert findings and findings[0].check == "de-specialization"
    assert findings[0].severity == "error"


def test_mutation_narrowed_dtype():
    """An f32 round-trip smuggled into an f64 executor is a dtype
    contract violation."""
    plan = lower(PAPER_STENCILS["jacobi2d"], backend="ref")
    assert not jaxpr_lint.lint_dtype(plan)
    from jax import enable_x64
    with enable_x64():
        corrupted = jax.make_jaxpr(
            lambda g: _plan.execute(
                plan, g.astype(jnp.float32).astype(jnp.float64)))(
            np.zeros(plan.shape))
    findings = jaxpr_lint.lint_dtype(plan, corrupted)
    assert findings and findings[0].check == "dtype-contract"
    assert "float64 -> float32" in findings[0].message


def test_mutation_forced_hbm_roundtrip():
    """Passing the staged chain off as the fused executor (no byte
    saving) must trip the HBM round-trip comparison."""
    pipe = PAPER_PIPELINES["reaction_diffusion2d"]
    plan = lower(pipe, backend="pallas", sweeps=1)
    assert not jaxpr_lint.lint_hbm(plan)

    def fake_staged(g):      # "fallback" identical to the fused path
        return _plan.execute(plan, g)

    findings = jaxpr_lint.lint_hbm(plan, staged_fn=fake_staged)
    assert findings and findings[0].check == "hbm-roundtrips"


# ---------------------------------------------------------------------------
# Wiring: lower() verifies every cache miss, caches the report
# ---------------------------------------------------------------------------
def _unique_spec(tag):
    return dataclasses.replace(
        PAPER_STENCILS["jacobi2d"], name=f"analysis_{tag}")


def test_second_identical_lower_zero_analyses():
    spec = _unique_spec("cache")
    analysis.clear_reports()
    p1 = lower(spec)
    assert analysis.counters()["verifications"] == 1
    p2 = lower(spec)           # plan-cache hit: no new analysis at all
    assert p1 is p2
    assert analysis.counters()["verifications"] == 1
    rep = analysis.report_for(p1)
    assert rep is not None and rep.ok


def test_strict_mode_raises_and_does_not_cache(monkeypatch):
    spec = _unique_spec("strict")
    bad = lambda plan: [verify.Finding("always-bad", "error", "seeded")]
    monkeypatch.setitem(verify.CHECKS, "always-bad", bad)
    analysis.set_verify_mode("strict")
    try:
        key_count = len(_plan.PLAN_CACHE.keys())
        with pytest.raises(analysis.PlanVerificationError) as ei:
            lower(spec)
        assert "always-bad" in str(ei.value)
        assert ei.value.report.errors
        # the offending plan never entered the plan cache
        assert len(_plan.PLAN_CACHE.keys()) == key_count
        with pytest.raises(analysis.PlanVerificationError):
            lower(spec)
    finally:
        analysis.set_verify_mode(None)


def test_warn_mode_warns(monkeypatch):
    spec = _unique_spec("warn")
    bad = lambda plan: [verify.Finding("always-bad", "error", "seeded")]
    monkeypatch.setitem(verify.CHECKS, "always-bad", bad)
    analysis.set_verify_mode("warn")
    try:
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            p = lower(spec)
    finally:
        analysis.set_verify_mode(None)
    msgs = [x for x in w
            if issubclass(x.category, analysis.PlanVerificationWarning)]
    assert msgs and "always-bad" in str(msgs[0].message)
    assert p is not None          # warn mode still lowers and caches


def test_off_mode_skips():
    spec = _unique_spec("off")
    analysis.clear_reports()
    analysis.set_verify_mode("off")
    try:
        p = lower(spec)
        assert analysis.counters()["verifications"] == 0
        assert analysis.report_for(p) is None
    finally:
        analysis.set_verify_mode(None)


def test_verify_mode_resolution(monkeypatch):
    monkeypatch.setenv(analysis.VERIFY_ENV, "strict")
    assert analysis.verify_mode() == "strict"
    analysis.set_verify_mode("warn")
    try:
        assert analysis.verify_mode() == "warn"
    finally:
        analysis.set_verify_mode(None)
    assert analysis.verify_mode() == "strict"
    monkeypatch.setenv(analysis.VERIFY_ENV, "bogus")
    with pytest.raises(ValueError):
        analysis.verify_mode()


# ---------------------------------------------------------------------------
# Layer-2 plumbing
# ---------------------------------------------------------------------------
def test_count_primitive_recurses_into_nested_jaxprs():
    plan = lower(PAPER_STENCILS["jacobi2d"], backend="pallas")
    jaxpr = jaxpr_lint.trace_plan_jaxpr(plan)
    assert jaxpr_lint.count_primitive(jaxpr, "pallas_call") >= 1
    assert (jaxpr_lint.count_tap_windows(jaxpr)
            <= jaxpr_lint.slice_budget(plan))
    # slices inside a run_plan scan body are only visible by recursing
    # into the ClosedJaxpr carried in the scan eqn's params
    ref = lower(PAPER_STENCILS["jacobi2d"], backend="ref")
    scanned = jaxpr_lint.trace_plan_jaxpr(ref, iters=4 * ref.sweeps)
    assert jaxpr_lint.count_primitive(scanned, "scan") >= 1
    assert jaxpr_lint.count_tap_windows(scanned) > 0


def test_fma_contraction_flagged_as_info():
    plan = lower(PAPER_STENCILS["jacobi2d"], backend="ref")
    findings = jaxpr_lint.lint_fma_contraction(plan)
    assert findings and findings[0].severity == "info"
    assert "atol=1e-12" in findings[0].message
    # a single fused block has no scan: nothing to flag
    assert not jaxpr_lint.lint_fma_contraction(plan, iters=plan.sweeps)


def test_lint_plan_skips_vm_and_distributed():
    p = lower(PAPER_STENCILS["jacobi1d"], backend="vm")
    rep = jaxpr_lint.lint_plan(p)
    assert rep.ok and any(f.check == "jaxpr-lint" for f in rep.infos)
    mesh = Mesh(np.array(jax.devices()[:1]), ("sx",))
    p = lower(PAPER_STENCILS["jacobi1d"], mesh=mesh, grid_axes=("sx",))
    rep = jaxpr_lint.lint_plan(p)
    assert rep.ok and any(f.check == "jaxpr-lint" for f in rep.infos)


def test_engine_analyze_end_to_end():
    eng = CasperEngine(PAPER_STENCILS["blur2d"], backend="pallas",
                       sweeps=2)
    rep = eng.analyze((24, 40), jnp.float64)
    assert isinstance(rep, analysis.Report)
    assert rep.ok, rep.pretty()
    assert set(verify.CHECKS) <= set(rep.checks_run)
    assert set(jaxpr_lint.LINT_CHECKS) <= set(rep.checks_run)
    d = rep.as_dict()
    assert d["ok"] and d["plan"].startswith("blur2d")


# ---------------------------------------------------------------------------
# The resident set charges the aligned DMA buffer
# ---------------------------------------------------------------------------
def test_vmem_residency_charges_aligned_fetch_buffer():
    """The kernel DMAs each window rounded out to the HBM granule
    ((8, 128) for f32 rank 2), so the resident set charges that buffer
    on top of three live windows (window, accumulator, tap temporary),
    each padded to whole vregs, and the double-buffered output block."""
    spec = PAPER_STENCILS["jacobi2d"]           # star, halo (1, 1)
    tile = (32, 256)
    assert pm.fetch_window(tile, (4, 4), 4) == (48, 512)
    # the 40 x 264 window takes 40 x 384 in whole (8, 128) vregs
    expect = (48 * 512 * 4 + 3 * (40 * 384) * 4 + 2 * 32 * 256 * 4)
    assert pm.vmem_residency(tile, spec.halo, 4, 4, 1) == expect
    # bf16 packs two rows per sublane: the row granule doubles
    assert pm.fetch_grain(2, 2) == (16, 128)
    assert pm.fetch_grain(1, 4) == (1024,)
    assert pm.fetch_grain(3, 4) == (1, 8, 128)


@pytest.mark.parametrize("tile,fits", [((16, 32, 512), True),
                                       ((8, 64, 512), True),
                                       ((32, 32, 512), False)])
def test_vmem_residency_refuses_what_mosaic_refuses(tile, fits):
    """heat3d at sweeps=4: Mosaic for the v5e allocates 12.00 and 13.60
    MiB of scoped VMEM for the first two tiles and refuses the third
    (20.97 MiB over the 16 MiB limit); the model agrees on each."""
    spec = PAPER_STENCILS["heat3d"]
    vmem = pm.vmem_residency(tile, spec.halo, 4, 4, 1)
    assert (vmem <= pm.TPU_VMEM_BYTES) is fits
    assert math.isinf(pm.pallas_tile_cost(spec, (512, 512, 512), tile,
                                          sweeps=4)) is not fits


def test_verifier_vmem_check_uses_residency_math():
    """The layer-1 vmem check and the autotuner reject the same tile."""
    spec = PAPER_STENCILS["jacobi2d"].with_boundary("periodic")
    p = _plan.lower(spec, (1024, 1024), jnp.float32, backend="pallas",
                    tile=(512, 512))
    rep = analysis.report_for(p) or analysis.verify_plan(p)
    assert rep.ok, rep.pretty()
    mut = dataclasses.replace(p, tile=(1024, 1024))
    assert "vmem-budget" in {f.check for f in
                             analysis.verify_plan(mut).errors}


# ---------------------------------------------------------------------------
# Slab-streaming invariants (the "slabs" check) — mutation coverage
# ---------------------------------------------------------------------------
def _streamed_plan(backend="ref"):
    """A clean "stream-from-host" plan: jacobi2d forced past a quarter-
    grid budget (the slab axes are unaffected by backend)."""
    import os
    shape = SHAPES[2]
    budget = 64 * 128 * 8 // 4
    old = os.environ.get(pm.SLAB_BUDGET_ENV)
    os.environ[pm.SLAB_BUDGET_ENV] = str(budget)
    try:
        p = lower(PAPER_STENCILS["jacobi2d"], backend=backend)
    finally:
        if old is None:
            os.environ.pop(pm.SLAB_BUDGET_ENV, None)
        else:
            os.environ[pm.SLAB_BUDGET_ENV] = old
    assert p.streams_from_host
    return p


def test_clean_streamed_plans_zero_findings():
    for backend in ("ref", "pallas"):
        p = _streamed_plan(backend)
        rep = analysis.report_for(p) or analysis.verify_plan(p)
        assert not rep.errors, rep.pretty()
        assert not rep.warnings, rep.pretty()


def test_mutation_gapped_slab_cover():
    clean = _streamed_plan()
    slabs = list(clean.slabs)
    s0, s1 = slabs[1]
    slabs[1] = (s0 + 1, s1)                  # row s0 covered by no slab
    assert "slabs" in _errors_of(
        dataclasses.replace(clean, slabs=tuple(slabs)))


def test_mutation_overlapping_slab_cover():
    clean = _streamed_plan()
    slabs = list(clean.slabs)
    s0, s1 = slabs[1]
    slabs[1] = (s0 - 1, s1)                  # row s0-1 covered twice
    assert "slabs" in _errors_of(
        dataclasses.replace(clean, slabs=tuple(slabs)))
    # cover must also start at 0 and end at shape[0]
    assert "slabs" in _errors_of(
        dataclasses.replace(clean, slabs=clean.slabs[:-1]))


def test_mutation_shallow_slab_overlap():
    clean = _streamed_plan()
    assert clean.slab_overlap == clean.deep_halo[0]
    assert "slabs" in _errors_of(
        dataclasses.replace(clean, slab_overlap=clean.slab_overlap - 1))


def test_mutation_slab_resident_over_budget():
    clean = _streamed_plan()
    # merge the cover into one whole-grid slab: still exact, but its
    # double-buffered resident set is ~3x the grid — far over budget
    mut = dataclasses.replace(clean, slabs=((0, clean.shape[0]),))
    assert "slabs" in _errors_of(mut)


def test_mutation_non_streamed_plan_carrying_slabs():
    clean = lower(PAPER_STENCILS["jacobi2d"], backend="ref")
    assert clean.slabs is None
    assert "slabs" in _errors_of(
        dataclasses.replace(clean, slabs=((0, 64),)))
    assert "slabs" in _errors_of(
        dataclasses.replace(clean, slab_overlap=2))


def test_lint_plan_skips_streamed():
    # slab-streamed plans execute through eager host staging: layer 2
    # declares the skip as an info instead of tracing
    p = _streamed_plan()
    rep = jaxpr_lint.lint_plan(p)
    assert rep.ok and any(f.check == "jaxpr-lint" for f in rep.infos)
    assert any("slab" in f.message for f in rep.infos)

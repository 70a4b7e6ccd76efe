"""Layer-2 static plan lint: trace the plan's jitted executor and walk
the jaxpr for properties the layer-1 field checks cannot see:

* **de-specialization** — a structured plan whose traced executor emits
  more tap-window slices (``slice`` eqns under the
  ``ref.TAP_WINDOW_SCOPE`` name scope) than its factored tap-op budget
  (``sweeps * sum_k tap_ops(stage_k)``): the compute core silently fell
  back to the dense per-tap chain.  This generalizes the one-off jaxpr
  slice-count guard of ``tests/test_structure.py`` into a real pass.
* **dtype-contract violations** — any narrowing float
  ``convert_element_type`` (f64 → f32/bf16/f16) inside an f64 plan:
  the repo-wide bit-identity contract runs entirely in f64.
* **cross-stage FMA contraction** — the ``run_plan`` scan composition
  rolls several fused blocks into one XLA computation, which licenses
  multiply-add contraction across the carried block boundary (the PR 6
  fuzz finding, seed 29: scan output matches the eager chain only to
  ``atol=1e-12``).  Flagged statically as an *info* finding on every
  scanned f64 plan — it is the documented contract, not a bug.
* **HBM round-trips** — a fused pipeline must move strictly fewer HBM
  bytes than its staged per-stage fallback (the whole point of fusion).
  Both executors are traced and their bytes counted as the chip moves
  them (:func:`hbm_bytes`): a kernel reads its operands and writes its
  results, its body works in VMEM.

The VM backend is numpy (untraceable) and distributed plans trace under
a mesh; both are skipped with an info finding.
"""
from __future__ import annotations

import contextlib

import numpy as np

from repro.core import plan as _plan
from repro.core import ref as _ref
from repro.core.stencil import factor_taps

from .verify import Finding, Report, summarize_plan

LINT_CHECKS = ("de-specialization", "dtype-contract", "fma-contraction",
               "hbm-roundtrips")


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------
def _subjaxprs(v):
    """Yield every jaxpr nested in an eqn param value: raw ``Jaxpr`` s
    (e.g. ``pallas_call``'s kernel), ``ClosedJaxpr`` s (scan/while/cond
    bodies) and containers of either."""
    if hasattr(v, "eqns"):
        yield v
    elif hasattr(v, "jaxpr"):
        yield from _subjaxprs(v.jaxpr)
    elif isinstance(v, (tuple, list)):
        for item in v:
            yield from _subjaxprs(item)


def _walk_eqns(jaxpr):
    """Depth-first over every eqn of ``jaxpr`` and all nested jaxprs."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in _subjaxprs(v):
                yield from _walk_eqns(sub)


def count_primitive(jaxpr, name: str) -> int:
    """Occurrences of primitive ``name`` in ``jaxpr`` (a ``Jaxpr`` or
    ``ClosedJaxpr``), recursing into scan/while/cond bodies and
    ``pallas_call`` kernel jaxprs."""
    inner = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    return sum(1 for eqn in _walk_eqns(inner) if eqn.primitive.name == name)


def count_tap_windows(jaxpr) -> int:
    """Tap-window fetches in ``jaxpr``: the ``slice`` eqns traced under
    the ``ref.TAP_WINDOW_SCOPE`` name scope, recursing like
    :func:`count_primitive` (the executors' other static slices — the
    kernel's window cut, mirror shifts, output crops — do not count)."""
    inner = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    return sum(1 for eqn in _walk_eqns(inner)
               if eqn.primitive.name == "slice"
               and _ref.TAP_WINDOW_SCOPE in str(eqn.source_info.name_stack))


def _x64_if_needed(dtype):
    if np.dtype(dtype).itemsize == 8:
        from jax import enable_x64
        return enable_x64()
    return contextlib.nullcontext()


def trace_plan_jaxpr(plan, iters: int | None = None):
    """The plan's executor as a ``ClosedJaxpr``: one fused block
    (``plan.execute``), or ``run_plan`` over ``iters`` total
    applications (the scan composition) when ``iters`` is given."""
    import jax
    if iters is None:
        def fn(g):
            return _plan.execute(plan, g)
    else:
        def fn(g):
            return _plan.run_plan(plan, g, iters)
    with _x64_if_needed(plan.dtype):
        dummy = np.zeros(plan.shape, np.dtype(plan.dtype))
        return jax.make_jaxpr(fn)(dummy)


# ---------------------------------------------------------------------------
# The checks
# ---------------------------------------------------------------------------
def _stage_slice_budget(stage) -> int:
    """Tap-window slice budget of ONE application of ``stage``: one
    fetch per factored tap-op plus the window re-centers — one per
    application (the shrinking deep-halo window) and, on the factored
    separable path, one per sequential 1-D axis pass.  Star/dense specs
    run the dense per-tap chain (``tap_ops == n_taps``, no axis
    passes).  Anything above this bound means the compute core
    de-specialized to a denser tap walk."""
    fz = factor_taps(stage)
    terms = fz.compute_terms
    passes = 0 if terms is None else sum(len(t.factors) for t in terms)
    return fz.tap_ops + passes + 1


def slice_budget(plan) -> int:
    """Upper bound on tap-window slices one fused block
    (``plan.execute``) may emit: the per-stage budget times ``sweeps``
    applications."""
    return plan.sweeps * sum(_stage_slice_budget(s) for s in plan.stages)


def lint_despecialization(plan, jaxpr=None) -> list[Finding]:
    if jaxpr is None:
        jaxpr = trace_plan_jaxpr(plan)
    budget = slice_budget(plan)
    n = count_tap_windows(jaxpr)
    if n > budget:
        return [Finding(
            "de-specialization", "error",
            f"traced executor emits {n} tap-window slices; the "
            f"factored budget is {budget} (sweeps={plan.sweeps}, "
            f"per-stage budgets "
            f"{[_stage_slice_budget(s) for s in plan.stages]}) — the "
            f"compute core de-specialized to a denser tap walk")]
    return []


def lint_dtype(plan, jaxpr=None) -> list[Finding]:
    if np.dtype(plan.dtype) != np.dtype("float64"):
        return []
    if jaxpr is None:
        jaxpr = trace_plan_jaxpr(plan)
    inner = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    narrowings: dict[str, int] = {}
    for eqn in _walk_eqns(inner):
        if eqn.primitive.name != "convert_element_type":
            continue
        old = np.dtype(eqn.invars[0].aval.dtype)
        new = np.dtype(eqn.params["new_dtype"])
        if (np.issubdtype(old, np.floating)
                and np.issubdtype(new, np.floating)
                and new.itemsize < old.itemsize):
            key = f"{old.name} -> {new.name}"
            narrowings[key] = narrowings.get(key, 0) + 1
    return [Finding(
        "dtype-contract", "error",
        f"f64 plan contains {n} narrowing float convert(s) {key}: the "
        f"f64 bit-identity contract is broken")
        for key, n in sorted(narrowings.items())]


def lint_fma_contraction(plan, iters: int | None = None) -> list[Finding]:
    """Flag the scan-composition contraction sites statically (info:
    this is the documented ``atol=1e-12`` contract of ``run_plan``, not
    a defect — see the PR 6 fuzz corpus, seed 29)."""
    if np.dtype(plan.dtype) != np.dtype("float64"):
        return []
    if iters is None:
        iters = 2 * plan.sweeps
    q, _ = plan.decompose(iters)
    if q < 2:
        return []
    jaxpr = trace_plan_jaxpr(plan, iters=iters)
    n_scans = count_primitive(jaxpr, "scan")
    if not n_scans:
        return []
    apps = plan.sweeps * len(plan.stages)
    return [Finding(
        "fma-contraction", "info",
        f"run_plan(iters={iters}) rolls {q} fused blocks ({apps} "
        f"applications each) into {n_scans} lax.scan(s): XLA may "
        f"contract multiply-adds across the carried block boundary, so "
        f"the scan path is held to atol=1e-12 instead of f64 "
        f"bit-identity (fuzz corpus seed 29)")]


def _aval_bytes(v) -> int:
    aval = getattr(v, "aval", None)
    shape = getattr(aval, "shape", None)
    if shape is None or not hasattr(aval, "dtype"):
        return 0
    return int(np.prod(shape, dtype=np.int64)) * np.dtype(aval.dtype).itemsize


def hbm_bytes(jaxpr) -> int:
    """HBM bytes of a traced executor, counted from its array operands:
    every ``pallas_call`` is charged its operands read once and its
    results written once (the kernel body is not walked), every other
    array op reads its operands and writes its result, and call-like
    eqns (``pjit``, ``custom_vmap_call``, ...) are walked instead of
    counted.  For kernels this is a lower bound: the kernel DMAs each
    tile's granule-aligned window (``perfmodel.fetch_window``), so
    overlapping windows re-read the halo and the alignment padding.
    Unfused XLA ops are counted one by one — an upper bound.  Both
    sides of a comparison are counted alike."""
    inner = jaxpr.jaxpr if hasattr(jaxpr, "jaxpr") else jaxpr
    total = 0
    for eqn in inner.eqns:
        subs = [sub for v in eqn.params.values() for sub in _subjaxprs(v)]
        if subs and eqn.primitive.name != "pallas_call":
            total += sum(hbm_bytes(sub) for sub in subs)
            continue
        total += sum(_aval_bytes(v) for v in eqn.invars)
        total += sum(_aval_bytes(v) for v in eqn.outvars)
    return total


def lint_hbm(plan, staged_fn=None) -> list[Finding]:
    """Trace one fused block and its staged per-stage fallback and
    compare their HBM bytes (:func:`hbm_bytes`): fusion must move
    strictly fewer (intermediates staying in VMEM is the whole point).
    ``staged_fn`` overrides the fallback executor (the mutation tests
    pass the fused executor itself to prove the check has teeth)."""
    import jax

    if staged_fn is None:
        def staged_fn(g):
            out = g
            for _ in range(plan.sweeps):
                for k in range(len(plan.stages)):
                    out = _plan.execute(plan.stage_plan(k), out)
            return out

    def fused_fn(g):
        return _plan.execute(plan, g)

    with _x64_if_needed(plan.dtype):
        dummy = jax.ShapeDtypeStruct(plan.shape, np.dtype(plan.dtype))
        fused = hbm_bytes(jax.make_jaxpr(fused_fn)(dummy))
        staged = hbm_bytes(jax.make_jaxpr(staged_fn)(dummy))
    if fused >= staged:
        return [Finding(
            "hbm-roundtrips", "error",
            f"fused pipeline moves {fused} HBM bytes but its "
            f"staged per-stage fallback moves {staged}: "
            f"fusion is not eliding the intermediate round-trips")]
    return []


# ---------------------------------------------------------------------------
# Entry point
# ---------------------------------------------------------------------------
def lint_plan(plan, hbm: bool | None = None) -> Report:
    """Run the full layer-2 lint over ``plan`` and return a
    :class:`~repro.analysis.verify.Report`.

    ``hbm=None`` runs the HBM round-trip comparison exactly when it is
    meaningful: a fused single-device Pallas pipeline.  (The staged
    fallback of a ``ref`` pipeline is *defined* as the chain, and
    distributed plans trace under a mesh.)
    """
    findings: list[Finding] = []
    if plan.backend == "vm":
        findings.append(Finding(
            "jaxpr-lint", "info",
            "vm backend executes in numpy; jaxpr lint skipped (the SPU "
            "program is verified by the layer-1 program check)"))
        return Report(summarize_plan(plan), ("jaxpr-lint",),
                      tuple(findings))
    if plan.is_distributed:
        findings.append(Finding(
            "jaxpr-lint", "info",
            "distributed plan: jaxpr lint runs on the single-device "
            "lowering (trace the shard-local path under its mesh to "
            "inspect collectives)"))
        return Report(summarize_plan(plan), ("jaxpr-lint",),
                      tuple(findings))
    if plan.needs_host_streaming:
        findings.append(Finding(
            "jaxpr-lint", "info",
            "slab-streamed plan executes through eager host staging "
            "(jax.device_put per slab cannot be traced); jaxpr lint "
            "skipped — the slab cover/overlap/residency invariants are "
            "verified by the layer-1 slabs check"))
        return Report(summarize_plan(plan), ("jaxpr-lint",),
                      tuple(findings))

    jaxpr = trace_plan_jaxpr(plan)
    findings += lint_despecialization(plan, jaxpr)
    findings += lint_dtype(plan, jaxpr)
    findings += lint_fma_contraction(plan)
    if hbm is None:
        hbm = (plan.is_pipeline and plan.fused
               and plan.backend in _plan.KERNEL_BACKENDS)
    if hbm:
        findings += lint_hbm(plan)
    return Report(summarize_plan(plan), LINT_CHECKS, tuple(findings))

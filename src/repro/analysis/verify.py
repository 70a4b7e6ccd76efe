"""Layer-1 static plan verification: symbolically re-derive every
invariant an :class:`~repro.core.plan.ExecutionPlan` is supposed to
satisfy and compare against what lowering actually produced.

The checks never execute the plan — they re-run the *derivations*
(window arithmetic, iteration decomposition, strategy decisions, tap
factorization, distributed feasibility) from the plan's primary inputs
(the spec's raw taps, the grid shape, the mesh) and flag any field that
disagrees.  ``plan.lower()`` calls :func:`verify_and_record` on every
cache miss, so a plan that violates its own invariants is caught at
lowering time: ``strict`` mode raises :class:`PlanVerificationError`,
the default ``warn`` mode emits :class:`PlanVerificationWarning` s, and
``off`` disables the pass (``CASPER_VERIFY`` env var or
:func:`set_verify_mode`).

Reports are cached per plan — a second identical ``lower()`` is a plan
cache hit and re-runs zero analyses (pinned by
``tests/test_analysis.py``).  Findings carry one of three severities:

* ``error``   — a plan invariant is violated (the mutation-test suite
  seeds these; the clean paper matrix must produce none),
* ``warning`` — legal but suspicious (e.g. a multi-hop halo exchange
  reaching past the grid edge),
* ``info``    — observations (e.g. specialization deliberately left on
  the table by ``structure="dense"``).
"""
from __future__ import annotations

import dataclasses
import os
import threading
import warnings
from collections import OrderedDict
from typing import Callable

import numpy as np

from repro.core import perfmodel as _pm
from repro.core import plan as _plan
from repro.core.stencil import factor_taps, parse_boundary

VERIFY_MODES = ("strict", "warn", "off")
SEVERITIES = ("error", "warning", "info")

#: Env var consulted (at verification time) when no explicit mode was
#: set through :func:`set_verify_mode`.
VERIFY_ENV = "CASPER_VERIFY"


# ---------------------------------------------------------------------------
# Findings and reports
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Finding:
    """One verification finding: which invariant (``check``), how bad
    (``severity``), and what exactly disagreed (``message``)."""

    check: str
    severity: str
    message: str

    def __str__(self) -> str:
        return f"[{self.severity}] {self.check}: {self.message}"

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


@dataclasses.dataclass(frozen=True)
class Report:
    """The outcome of analyzing one plan: which checks ran and every
    finding they produced.  ``ok`` means zero *error* findings."""

    plan_summary: str
    checks_run: tuple[str, ...]
    findings: tuple[Finding, ...]

    @property
    def errors(self) -> tuple[Finding, ...]:
        return tuple(f for f in self.findings if f.severity == "error")

    @property
    def warnings(self) -> tuple[Finding, ...]:
        return tuple(f for f in self.findings if f.severity == "warning")

    @property
    def infos(self) -> tuple[Finding, ...]:
        return tuple(f for f in self.findings if f.severity == "info")

    @property
    def ok(self) -> bool:
        return not self.errors

    def merged(self, other: "Report") -> "Report":
        """Combine with another report over the same plan (layer 1 +
        layer 2)."""
        return Report(self.plan_summary,
                      self.checks_run + other.checks_run,
                      self.findings + other.findings)

    def as_dict(self) -> dict:
        return {
            "plan": self.plan_summary,
            "ok": self.ok,
            "checks_run": list(self.checks_run),
            "findings": [f.as_dict() for f in self.findings],
        }

    def pretty(self) -> str:
        lines = [f"plan {self.plan_summary}: "
                 f"{len(self.checks_run)} checks, "
                 f"{len(self.errors)} errors, {len(self.warnings)} "
                 f"warnings, {len(self.infos)} infos"]
        lines += [f"  {f}" for f in self.findings]
        return "\n".join(lines)


class PlanVerificationError(RuntimeError):
    """Raised (strict mode) when a freshly lowered plan violates an
    invariant; carries the full :class:`Report` as ``.report``."""

    def __init__(self, report: Report):
        super().__init__(report.pretty())
        self.report = report


class PlanVerificationWarning(UserWarning):
    """Emitted (default ``warn`` mode) once per error finding."""


def summarize_plan(plan) -> str:
    name = getattr(plan.spec, "name", "?")
    mesh = "" if plan.mesh is None else " distributed"
    return (f"{name}@{plan.shape} {plan.dtype} {plan.backend} "
            f"sweeps={plan.sweeps}{mesh}")


# ---------------------------------------------------------------------------
# Mode control, counters, report cache
# ---------------------------------------------------------------------------
_MODE_OVERRIDE: str | None = None
_LOCK = threading.RLock()
_REPORTS: OrderedDict = OrderedDict()       # plan -> layer-1 Report
_REPORTS_MAXSIZE = 512
_COUNTERS = {"verifications": 0, "report_cache_hits": 0}


def verify_mode() -> str:
    """The active mode: :func:`set_verify_mode` override, else the
    ``CASPER_VERIFY`` env var, else ``"warn"``."""
    mode = _MODE_OVERRIDE or os.environ.get(VERIFY_ENV, "warn")
    if mode not in VERIFY_MODES:
        raise ValueError(f"unknown verify mode {mode!r}; expected one of "
                         f"{VERIFY_MODES}")
    return mode


def set_verify_mode(mode: str | None) -> None:
    """Override the verification mode process-wide (``None`` restores
    the env-var/default resolution)."""
    global _MODE_OVERRIDE
    if mode is not None and mode not in VERIFY_MODES:
        raise ValueError(f"unknown verify mode {mode!r}; expected one of "
                         f"{VERIFY_MODES}")
    _MODE_OVERRIDE = mode


def counters() -> dict:
    """Snapshot of the analysis counters: ``verifications`` counts the
    layer-1 passes actually executed (cache hits don't re-run)."""
    with _LOCK:
        return dict(_COUNTERS)


def clear_reports() -> None:
    """Drop the per-plan report cache and zero the counters (tests)."""
    with _LOCK:
        _REPORTS.clear()
        for k in _COUNTERS:
            _COUNTERS[k] = 0


def report_for(plan) -> Report | None:
    """The cached layer-1 report for ``plan``, if it was ever verified."""
    with _LOCK:
        return _REPORTS.get(plan)


# ---------------------------------------------------------------------------
# The invariant catalog (layer 1)
# ---------------------------------------------------------------------------
CHECKS: "OrderedDict[str, Callable]" = OrderedDict()


def _check(name: str):
    def deco(fn):
        CHECKS[name] = fn
        return fn
    return deco


def _derived_stage_halos(plan) -> list[tuple[int, ...]]:
    """Per-stage halo radii re-derived from the raw taps (max |offset|
    per dim) — deliberately *not* read off ``spec.halo``."""
    ndim = len(plan.shape)
    return [tuple(max((abs(off[d]) for off, _ in s.taps), default=0)
                  for d in range(ndim))
            for s in plan.stages]


@_check("halo-arithmetic")
def _check_halo(plan) -> list[Finding]:
    """``plan.halo`` must equal the per-dim sum of stage radii (a single
    spec is its own one-stage chain) and ``deep_halo == sweeps * halo``
    exactly — the ``tile + 2*sweeps*sum(h_k)`` window arithmetic hangs
    off these two fields."""
    out = []
    per_stage = _derived_stage_halos(plan)
    derived = tuple(sum(h) for h in zip(*per_stage))
    if plan.halo != derived:
        out.append(Finding(
            "halo-arithmetic", "error",
            f"plan.halo={plan.halo} but the stage taps derive {derived}"))
    deep = tuple(plan.sweeps * h for h in derived)
    if plan.deep_halo != deep:
        out.append(Finding(
            "halo-arithmetic", "error",
            f"plan.deep_halo={plan.deep_halo} but sweeps*halo="
            f"{deep} (sweeps={plan.sweeps})"))
    return out


@_check("decompose")
def _check_decompose(plan) -> list[Finding]:
    """``decompose(iters) == (q, r)`` with ``iters == q*sweeps + r`` and
    ``0 <= r < sweeps``, exactly, for a spread of iteration counts."""
    out = []
    if plan.sweeps < 1:
        return [Finding("decompose", "error",
                        f"sweeps must be >= 1, got {plan.sweeps}")]
    for iters in (0, 1, plan.sweeps - 1, plan.sweeps, plan.sweeps + 1,
                  2 * plan.sweeps, 7 * plan.sweeps + 3):
        if iters < 0:
            continue
        q, r = plan.decompose(iters)
        if q * plan.sweeps + r != iters or not 0 <= r < plan.sweeps:
            out.append(Finding(
                "decompose", "error",
                f"decompose({iters}) = ({q}, {r}) violates "
                f"iters == q*{plan.sweeps} + r with 0 <= r < sweeps"))
    return out


@_check("tile-legality")
def _check_tile(plan) -> list[Finding]:
    """Only fused kernel-backend plans carry a resolved tile; its rank
    matches the grid, entries are positive, a pad-free kernel's wrapped
    fetch needs every extent to be a multiple of its tile and every tile
    to be at least its aligned fetch depth (else lowering should have
    fallen back to the padded window), and a compiled plan's tile must
    be a multiple of the HBM granule its DMAs are aligned to."""
    out = []
    needs_tile = plan.backend in _plan.KERNEL_BACKENDS and plan.fused
    if not needs_tile:
        if plan.tile is not None:
            out.append(Finding(
                "tile-legality", "error",
                f"{plan.backend}{'' if plan.fused else ' staged'} plan "
                f"must not carry a resolved tile, got {plan.tile}"))
        return out
    if plan.tile is None:
        return [Finding("tile-legality", "error",
                        f"fused {plan.backend} plan has no resolved tile")]
    if len(plan.tile) != len(plan.shape):
        return [Finding(
            "tile-legality", "error",
            f"tile rank {len(plan.tile)} != grid rank {len(plan.shape)}")]
    if any(t < 1 for t in plan.tile):
        return [Finding("tile-legality", "error",
                        f"tile entries must be positive, got {plan.tile}")]
    itemsize = np.dtype(plan.dtype).itemsize
    grain = _pm.fetch_grain(len(plan.tile), itemsize)
    if plan.ghost_strategy == "pad-free" and not _pm.pad_free_fetch(
            plan.shape, plan.tile, plan.deep_halo, itemsize):
        out.append(Finding(
            "tile-legality", "error",
            f"pad-free fetch needs grid {plan.shape} to be a multiple of "
            f"tile {plan.tile} with every tile >= its fetch depth "
            f"{_pm.fetch_halo(plan.deep_halo, grain)}"))
    if not plan.interpret and any(t % g for t, g in zip(plan.tile, grain)):
        out.append(Finding(
            "tile-legality", "error",
            f"compiled tile {plan.tile} is not a multiple of the HBM "
            f"granule {grain}"))
    return out


@_check("vmem-budget")
def _check_vmem(plan) -> list[Finding]:
    """The fused kernel's resident set — DMA buffer, window,
    accumulator, per-term intermediates, output block — must fit
    VMEM."""
    if not (plan.backend in _plan.KERNEL_BACKENDS and plan.fused
            and plan.tile is not None):
        return []
    itemsize = np.dtype(plan.dtype).itemsize
    n_terms = max(
        (1 if s.factorization.compute_terms is None
         else len(s.factorization.compute_terms)) for s in plan.stages)
    vmem = _pm.vmem_residency(plan.tile, plan.halo, plan.sweeps, itemsize,
                              n_terms)
    if vmem > _pm.TPU_VMEM_BYTES:
        return [Finding(
            "vmem-budget", "error",
            f"resident set {vmem} B exceeds VMEM "
            f"{_pm.TPU_VMEM_BYTES} B (tile={plan.tile}, "
            f"window={_pm.tile_window(plan.tile, plan.halo, plan.sweeps)}, "
            f"terms={n_terms})")]
    return []


@_check("ghost-strategy")
def _check_ghost(plan) -> list[Finding]:
    """Re-run the ghost-strategy decision from the plan's primary
    inputs and compare: ``ref`` pads, ``vm`` streams, a non-fusable
    pipeline stages, distributed Pallas always takes the padded window,
    a single-device ref/pallas grid past the recorded slab budget
    streams from host, and single-device Pallas otherwise re-derives
    pad-free vs padded-window."""
    g = plan.ghost_strategy
    if g not in _plan.GHOST_STRATEGIES:
        return [Finding("ghost-strategy", "error",
                        f"unknown ghost strategy {g!r}")]
    itemsize = np.dtype(plan.dtype).itemsize
    over_budget = (plan.slab_budget is not None
                   and int(np.prod(plan.shape)) * itemsize
                   > plan.slab_budget)
    if plan.is_pipeline and not plan.fused:
        expected = "staged"
    elif (over_budget and not plan.is_distributed
          and plan.backend in ("ref",) + _plan.KERNEL_BACKENDS):
        expected = "stream-from-host"
    elif plan.backend == "ref":
        expected = "pad"
    elif plan.backend == "vm":
        expected = "stream"
    elif plan.is_distributed:
        expected = "padded-window"
    else:
        expected = _plan.ghost_strategy_for(
            plan.spec, plan.shape, np.dtype(plan.dtype).itemsize,
            plan.sweeps, plan.tile)
    if g != expected:
        return [Finding(
            "ghost-strategy", "error",
            f"ghost strategy {g!r} but re-derivation says {expected!r} "
            f"(backend={plan.backend}, boundary={plan.boundary_mode}, "
            f"fused={plan.fused}, distributed={plan.is_distributed})")]
    return []


@_check("slabs")
def _check_slabs(plan) -> list[Finding]:
    """Slab-streaming invariants (ISSUE 8): a ``stream-from-host`` plan
    must carry an *exact* contiguous slab cover of the outermost axis,
    an overlap exactly ``sweeps * halo`` deep (the slab boundary is a
    halo against host memory), and per-slab streaming resident bytes
    within the recorded budget (a single-row slab is irreducible and
    exempt); every other plan must carry no slab fields."""
    if not plan.streams_from_host:
        return [Finding("slabs", "error",
                        f"non-streamed plan carries {field}={val!r}")
                for field, val in (("slabs", plan.slabs),
                                   ("slab_overlap", plan.slab_overlap))
                if val is not None]
    if plan.slab_budget is None:
        return [Finding("slabs", "error",
                        "streamed plan records no slab_budget")]
    if not plan.slabs:
        return [Finding("slabs", "error",
                        f"streamed plan has no slab cover: {plan.slabs!r}")]
    out = []
    prev_stop = 0
    for start, stop in plan.slabs:
        if start != prev_stop:
            out.append(Finding(
                "slabs", "error",
                f"slab cover {'gap' if start > prev_stop else 'overlap'} "
                f"at {start} (previous slab stops at {prev_stop})"))
        if stop <= start:
            out.append(Finding("slabs", "error",
                               f"empty slab ({start}, {stop})"))
        prev_stop = stop
    if prev_stop != plan.shape[0]:
        out.append(Finding(
            "slabs", "error",
            f"slab cover stops at {prev_stop}, grid outermost extent is "
            f"{plan.shape[0]}"))
    deep0 = plan.deep_halo[0]
    if plan.slab_overlap != deep0:
        out.append(Finding(
            "slabs", "error",
            f"slab overlap {plan.slab_overlap} != sweeps*halo depth "
            f"{deep0} (sweeps={plan.sweeps}, halo={plan.halo})"))
    itemsize = np.dtype(plan.dtype).itemsize
    for start, stop in plan.slabs:
        length = stop - start
        resident = _pm.slab_resident_bytes(length, plan.shape,
                                           plan.deep_halo, itemsize)
        if resident > plan.slab_budget and length > 1:
            out.append(Finding(
                "slabs", "error",
                f"slab ({start}, {stop}) streaming resident set "
                f"{resident} B exceeds budget {plan.slab_budget} B"))
    return out


@_check("fusability")
def _check_fusability(plan) -> list[Finding]:
    """Re-derive the pipeline fusability rule — fusable iff no stage is
    periodic or every stage is (between-stage ghosts restorable
    tile-locally) — and compare with ``plan.fused``."""
    if not plan.is_pipeline:
        if not plan.fused:
            return [Finding("fusability", "error",
                            "single-spec plan marked fused=False")]
        return []
    modes = [s.boundary_mode for s in plan.stages]
    fusable = all(m == "periodic" for m in modes) or all(
        m != "periodic" for m in modes)
    if plan.fused != fusable:
        return [Finding(
            "fusability", "error",
            f"fused={plan.fused} but stage boundary modes {modes} "
            f"re-derive fusable={fusable}")]
    return []


def _expand_terms(ndim: int, terms) -> dict:
    """Expand factor terms back to a dense ``offset -> coeff`` map (each
    term an outer product of its 1-D factors; terms sum)."""
    import itertools
    dense: dict = {}
    for term in terms:
        axes = [f.axis for f in term.factors]
        for combo in itertools.product(
                *[zip(f.offsets, f.coeffs) for f in term.factors]):
            off = [0] * ndim
            coeff = 1.0
            for ax, (o, c) in zip(axes, combo):
                off[ax] += o
                coeff *= c
            key = tuple(off)
            dense[key] = dense.get(key, 0.0) + coeff
    return dense


@_check("factorization")
def _check_factorization(plan) -> list[Finding]:
    """A single-spec plan pins ``factor_taps(spec)`` (the f64
    accumulation order); its terms must also *numerically* re-expand to
    the spec's dense tap set.  Pipelines carry no plan-level
    factorization (each stage keeps its own)."""
    out = []
    if plan.is_pipeline:
        if plan.factorization is not None:
            out.append(Finding(
                "factorization", "error",
                "pipeline plan must not carry a plan-level factorization"))
        return out
    spec = plan.spec
    expected = factor_taps(spec)
    if plan.factorization != expected:
        out.append(Finding(
            "factorization", "error",
            f"plan.factorization ({plan.factorization.structure}, "
            f"tap_ops={plan.factorization.tap_ops}) != factor_taps(spec) "
            f"({expected.structure}, tap_ops={expected.tap_ops})"))
    fz = plan.factorization
    if fz.terms is not None:
        dense = _expand_terms(spec.ndim, fz.terms)
        want = {off: c for off, c in spec.taps}
        keys = set(dense) | set(want)
        drift = max((abs(dense.get(k, 0.0) - want.get(k, 0.0))
                     for k in keys), default=0.0)
        scale = max((abs(c) for c in want.values()), default=1.0)
        if drift > 1e-9 * max(scale, 1.0):
            out.append(Finding(
                "factorization", "error",
                f"factored terms re-expand with max tap drift {drift:g} "
                f"vs the spec's dense taps"))
    if spec.structure == "dense" and spec.classified_structure != "dense":
        out.append(Finding(
            "factorization", "info",
            f"structure forced dense; classifier would specialize as "
            f"{spec.classified_structure!r} "
            f"(tap_ops {factor_taps(spec.with_structure('auto')).tap_ops} "
            f"vs {spec.n_taps})"))
    return out


@_check("distributed")
def _check_distributed(plan) -> list[Finding]:
    """Distributed feasibility: shard extents times mesh axis sizes
    reproduce the global grid, the per-axis exchange strategy matches
    the boundary mode on sharded dims (and is absent elsewhere), the
    mesh fingerprint is honest, and a multi-hop deep halo
    (``hops = ceil(deep/shard)``) that reaches past the grid edge on a
    non-wrap exchange is flagged as wasted collective launches."""
    out = []
    if plan.mesh is None:
        for field in ("grid_axes", "exchange", "shard_shape",
                      "mesh_fingerprint"):
            if getattr(plan, field) is not None:
                out.append(Finding(
                    "distributed", "error",
                    f"single-device plan carries {field}="
                    f"{getattr(plan, field)!r}"))
        return out
    axes = plan.grid_axes
    if axes is None or len(axes) != len(plan.shape):
        return [Finding("distributed", "error",
                        f"grid_axes {axes!r} does not cover the grid")]
    fp = _plan.mesh_fingerprint(plan.mesh, axes)
    if plan.mesh_fingerprint != fp:
        out.append(Finding(
            "distributed", "error",
            "mesh_fingerprint does not match the plan's mesh/grid_axes"))
    if plan.shard_shape is None:
        return out + [Finding("distributed", "error",
                              "distributed plan has no shard_shape")]
    for d, n in enumerate(plan.shape):
        size = plan.mesh.shape[axes[d]] if axes[d] is not None else 1
        if plan.shard_shape[d] * size != n:
            out.append(Finding(
                "distributed", "error",
                f"shard_shape[{d}]={plan.shard_shape[d]} x axis size "
                f"{size} != grid extent {n}"))
    staged = plan.is_pipeline and not plan.fused
    if staged:
        if plan.exchange is not None:
            out.append(Finding(
                "distributed", "error",
                "staged pipeline plan must not carry exchange strategies "
                "(its stage plans exchange)"))
        return out
    if plan.exchange is None:
        return out + [Finding("distributed", "error",
                              "fused distributed plan has no exchange "
                              "strategies")]
    for d in range(len(plan.shape)):
        expected = (_plan.exchange_strategy_for(plan.boundary_mode)
                    if axes[d] is not None else None)
        got = plan.exchange[d] if d < len(plan.exchange) else None
        if got != expected:
            out.append(Finding(
                "distributed", "error",
                f"exchange[{d}]={got!r} but boundary "
                f"{plan.boundary_mode!r} on axis {axes[d]!r} requires "
                f"{expected!r}"))
        if axes[d] is not None and plan.shard_shape[d] > 0:
            size = plan.mesh.shape[axes[d]]
            hops = -(-plan.deep_halo[d] // plan.shard_shape[d])
            if size > 1 and hops > size and got != "wrap-ring":
                out.append(Finding(
                    "distributed", "warning",
                    f"dim {d}: deep halo {plan.deep_halo[d]} needs "
                    f"{hops} exchange hops but the mesh axis only has "
                    f"{size} shards; fetches past the grid edge serve "
                    f"boundary fill (wasted collective launches)"))
    return out


@_check("program")
def _check_program(plan) -> list[Finding]:
    """The assembled SPU program must agree with the spec: one
    instruction per tap per stage, the stream plan recording the spec's
    boundary mode and tap-structure class, and the structured
    instruction count matching the factored MAC count."""
    out = []
    prog = plan.program
    if plan.is_pipeline:
        stages = getattr(prog, "stages", None)
        if stages is None or len(stages) != plan.spec.n_stages:
            return [Finding(
                "program", "error",
                f"pipeline program has "
                f"{'no' if stages is None else len(stages)} stages, spec "
                f"has {plan.spec.n_stages}")]
        pairs = zip(stages, plan.stages)
    else:
        pairs = [(prog, plan.spec)]
    for k, (p, s) in enumerate(pairs):
        where = f"stage {k} " if plan.is_pipeline else ""
        if p.n_instrs != s.n_taps:
            out.append(Finding(
                "program", "error",
                f"{where}program has {p.n_instrs} instructions for "
                f"{s.n_taps} taps"))
        if parse_boundary(p.boundary) != (s.boundary_mode,
                                          s.boundary_value):
            out.append(Finding(
                "program", "error",
                f"{where}program records boundary {p.boundary!r}, spec "
                f"says {s.boundary!r} (the VM serves out-of-grid "
                f"elements from the recorded string)"))
        fz = factor_taps(s)
        if p.structure != fz.structure:
            out.append(Finding(
                "program", "error",
                f"{where}program records structure {p.structure!r}, "
                f"factor_taps says {fz.structure!r}"))
        if p.structured_n_instrs != fz.tap_ops:
            out.append(Finding(
                "program", "error",
                f"{where}structured instruction count "
                f"{p.structured_n_instrs} != factored tap_ops "
                f"{fz.tap_ops}"))
    return out


@_check("plan-fields")
def _check_fields(plan) -> list[Finding]:
    """Field sanity: canonical dtype name, known backend, positive grid
    extents, spec rank matching the grid."""
    out = []
    if plan.backend not in _plan.BACKENDS:
        out.append(Finding("plan-fields", "error",
                           f"unknown backend {plan.backend!r}"))
    try:
        canonical = np.dtype(plan.dtype).name
    except TypeError:
        return out + [Finding("plan-fields", "error",
                              f"invalid dtype {plan.dtype!r}")]
    if plan.dtype != canonical:
        out.append(Finding(
            "plan-fields", "error",
            f"dtype {plan.dtype!r} not canonical ({canonical!r})"))
    if len(plan.shape) != plan.spec.ndim:
        out.append(Finding(
            "plan-fields", "error",
            f"grid rank {len(plan.shape)} != spec ndim {plan.spec.ndim}"))
    if any(n < 1 for n in plan.shape):
        out.append(Finding("plan-fields", "error",
                           f"grid extents must be positive: {plan.shape}"))
    return out


# ---------------------------------------------------------------------------
# Runner + lowering hook
# ---------------------------------------------------------------------------
def verify_plan(plan) -> Report:
    """Run the full layer-1 invariant catalog over ``plan`` (pure — no
    caching, no mode handling).  A check that itself crashes on a
    corrupted plan is reported as an error finding, never raised."""
    findings: list[Finding] = []
    for name, fn in CHECKS.items():
        try:
            findings.extend(fn(plan))
        except Exception as e:  # corrupted plans may break derivations
            findings.append(Finding(
                name, "error",
                f"check raised {type(e).__name__}: {e}"))
    return Report(summarize_plan(plan), tuple(CHECKS), tuple(findings))


def _verify_cached(plan) -> Report:
    with _LOCK:
        hit = _REPORTS.get(plan)
        if hit is not None:
            _REPORTS.move_to_end(plan)
            _COUNTERS["report_cache_hits"] += 1
            return hit
    report = verify_plan(plan)
    with _LOCK:
        _COUNTERS["verifications"] += 1
        _REPORTS[plan] = report
        while len(_REPORTS) > _REPORTS_MAXSIZE:
            _REPORTS.popitem(last=False)
    return report


def verify_and_record(plan) -> Report | None:
    """The ``plan.lower()`` hook: verify a freshly lowered plan per the
    active mode.  ``strict`` raises :class:`PlanVerificationError` (the
    plan is then never cached), ``warn`` emits one
    :class:`PlanVerificationWarning` per error finding, ``off`` skips
    the pass entirely.  Returns the (cached) report, or ``None`` when
    off."""
    mode = verify_mode()
    if mode == "off":
        return None
    report = _verify_cached(plan)
    if not report.ok:
        if mode == "strict":
            raise PlanVerificationError(report)
        for f in report.errors:
            warnings.warn(f"{report.plan_summary}: {f}",
                          PlanVerificationWarning, stacklevel=4)
    return report

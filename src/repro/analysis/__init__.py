"""Static plan analysis: prove every :class:`ExecutionPlan` before it
runs.

Two layers (see docs/analysis.md):

* :mod:`repro.analysis.verify` — layer 1, pure field/arithmetic
  re-derivation of the lowering invariants; runs automatically on every
  ``plan.lower()`` cache miss (``CASPER_VERIFY`` = ``strict`` / ``warn``
  / ``off``).
* :mod:`repro.analysis.jaxpr_lint` — layer 2, traces the jitted
  executor and walks the jaxpr / compiled HLO; on demand via
  :func:`analyze_plan`, ``CasperEngine.analyze()`` or
  ``tools/casper_lint.py``.
"""
from .verify import (
    CHECKS,
    Finding,
    PlanVerificationError,
    PlanVerificationWarning,
    Report,
    VERIFY_ENV,
    VERIFY_MODES,
    clear_reports,
    counters,
    report_for,
    set_verify_mode,
    summarize_plan,
    verify_and_record,
    verify_mode,
    verify_plan,
)
from .jaxpr_lint import (
    LINT_CHECKS,
    count_primitive,
    count_tap_windows,
    lint_plan,
    slice_budget,
    trace_plan_jaxpr,
)
from .serve_check import SHED_POLICIES, check_serve_config

__all__ = [
    "CHECKS", "LINT_CHECKS", "Finding", "PlanVerificationError",
    "PlanVerificationWarning", "Report", "VERIFY_ENV", "VERIFY_MODES",
    "SHED_POLICIES", "analyze_plan", "check_serve_config",
    "clear_reports", "count_primitive", "count_tap_windows", "counters",
    "lint_plan", "report_for", "set_verify_mode", "slice_budget",
    "summarize_plan", "trace_plan_jaxpr", "verify_and_record",
    "verify_mode", "verify_plan",
]


def analyze_plan(plan, lint: bool = True) -> Report:
    """The full analysis of one plan: the layer-1 invariant catalog
    (cached per plan) merged, when ``lint``, with the layer-2
    jaxpr/HLO lint."""
    from .verify import _verify_cached
    report = _verify_cached(plan)
    if lint:
        report = report.merged(lint_plan(plan))
    return report

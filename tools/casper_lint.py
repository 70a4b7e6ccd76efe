#!/usr/bin/env python
"""casper-lint: run the static plan analysis suite over the full paper
matrix as a CI gate.

Every PAPER_STENCILS spec × boundary mode × structure (auto / forced
dense) × backend (ref / pallas / vm), plus every PAPER_PIPELINES chain
(native boundaries and the rebased all-periodic / all-zero variants) ×
backend, is lowered and analyzed:

* layer 1 (``repro.analysis.verify``) on every plan — this also runs
  implicitly inside ``plan.lower()``; the tool re-reads the cached
  report;
* layer 2 (``repro.analysis.jaxpr_lint``) on every traceable plan —
  de-specialization, dtype contract, FMA contraction sites, and the
  fused-vs-staged HBM round-trip comparison for non-periodic Pallas
  pipelines.

Exit code is nonzero iff any *error* finding appears (warnings and
infos are reported but do not gate).  ``--out report.json`` writes the
full machine-readable report (uploaded as a CI artifact).

Usage:
    PYTHONPATH=src python tools/casper_lint.py [--strict] [--no-lint]
        [--fast] [--out report.json]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import jax.numpy as jnp

from repro import analysis
from repro.core import plan as _plan
from repro.core.stencil import PAPER_PIPELINES, PAPER_STENCILS

BOUNDARIES = ("zero", "constant(0.5)", "periodic", "reflect")
SHAPES = {1: (512,), 2: (64, 128), 3: (8, 16, 128)}
SWEEPS = (1, 2)
BACKENDS = ("ref", "pallas", "vm")


def iter_spec_cases(fast: bool):
    for name, spec in PAPER_STENCILS.items():
        shape = SHAPES[spec.ndim]
        for boundary in BOUNDARIES:
            for structure in ("auto", "dense"):
                for backend in BACKENDS:
                    for sweeps in SWEEPS:
                        if fast and (sweeps != 1 or structure != "auto"):
                            continue
                        s = spec.with_boundary(boundary)
                        if structure == "dense":
                            s = s.with_structure("dense")
                        yield (f"{name}/{boundary}/{structure}/{backend}"
                               f"/t{sweeps}", s, shape, backend, sweeps)


def iter_pipeline_cases(fast: bool):
    for name, pipe in PAPER_PIPELINES.items():
        variants = {"native": pipe}
        if not fast:
            # rebase every stage onto one mode: both fusable families
            # (all-periodic, all-non-periodic) plus the native chain
            import dataclasses
            for mode in ("periodic", "zero"):
                stages = tuple(s.with_boundary(mode) for s in pipe.stages)
                variants[mode] = dataclasses.replace(pipe, stages=stages)
        for vname, p in variants.items():
            for backend in BACKENDS:
                for sweeps in SWEEPS if not fast else (1,):
                    yield (f"{name}/{vname}/{backend}/t{sweeps}",
                           p, (64, 128), backend, sweeps)


def iter_slab_cases(fast: bool):
    """Slab-streamed variants: each case carries a forced
    ``CASPER_SLAB_BUDGET`` (a quarter of the f64 grid) that pushes the
    plan onto the ``"stream-from-host"`` ghost path, so the layer-1 slab
    invariants (exact cover, ``sweeps*halo`` overlap, per-slab residency)
    and the layer-2 streamed-plan skip are exercised by the CI gate.
    Only the ref and kernel backends stream (vm never leaves core)."""
    import math
    workloads = [("jacobi1d", "zero"), ("jacobi2d", "periodic"),
                 ("blur2d", "constant(0.5)"), ("star33_3d", "reflect")]
    if fast:
        workloads = workloads[:2]
    for name, boundary in workloads:
        spec = PAPER_STENCILS[name].with_boundary(boundary)
        shape = SHAPES[spec.ndim]
        budget = math.prod(shape) * 8 // 4
        for backend in ("ref", "pallas"):
            for sweeps in SWEEPS if not fast else (1,):
                yield (f"{name}/{boundary}/slab/{backend}/t{sweeps}",
                       spec, shape, backend, sweeps, budget)
    for name, pipe in PAPER_PIPELINES.items():
        shape = (64, 128)
        budget = math.prod(shape) * 8 // 4
        for backend in ("ref", "pallas"):
            yield (f"{name}/native/slab/{backend}/t1",
                   pipe, shape, backend, 1, budget)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--strict", action="store_true",
                    help="lower in strict mode: the first invariant "
                         "violation raises PlanVerificationError")
    ap.add_argument("--no-lint", action="store_true",
                    help="layer-1 verification only (no jaxpr/HLO lint)")
    ap.add_argument("--fast", action="store_true",
                    help="reduced matrix (sweeps=1, auto structure only)")
    ap.add_argument("--out", default=None, metavar="PATH",
                    help="write the JSON report here")
    args = ap.parse_args(argv)

    if args.strict:
        analysis.set_verify_mode("strict")

    t0 = time.time()
    reports: list[tuple[str, analysis.Report]] = []
    n_err = n_warn = n_info = 0
    cases = [c + (None,) for c in iter_spec_cases(args.fast)]
    cases += [c + (None,) for c in iter_pipeline_cases(args.fast)]
    cases += list(iter_slab_cases(args.fast))
    from repro.core import perfmodel as _pm
    for label, spec, shape, backend, sweeps, budget in cases:
        old = os.environ.get(_pm.SLAB_BUDGET_ENV)
        if budget is not None:
            os.environ[_pm.SLAB_BUDGET_ENV] = str(budget)
        try:
            plan = _plan.lower(spec, shape, jnp.float64, backend=backend,
                               sweeps=sweeps)
            report = analysis.analyze_plan(plan, lint=not args.no_lint)
        finally:
            if budget is not None:
                if old is None:
                    os.environ.pop(_pm.SLAB_BUDGET_ENV, None)
                else:
                    os.environ[_pm.SLAB_BUDGET_ENV] = old
        reports.append((label, report))
        n_err += len(report.errors)
        n_warn += len(report.warnings)
        n_info += len(report.infos)
        for f in report.errors + report.warnings:
            print(f"{label}: {f}")

    dt = time.time() - t0
    print(f"casper-lint: {len(reports)} plans analyzed in {dt:.1f}s — "
          f"{n_err} errors, {n_warn} warnings, {n_info} infos; "
          f"analysis counters {analysis.counters()}")

    if args.out:
        payload = {
            "n_plans": len(reports),
            "n_errors": n_err,
            "n_warnings": n_warn,
            "n_infos": n_info,
            "elapsed_s": dt,
            "counters": analysis.counters(),
            "reports": [dict(case=label, **r.as_dict())
                        for label, r in reports],
        }
        with open(args.out, "w") as fh:
            json.dump(payload, fh, indent=2)
        print(f"wrote {args.out}")

    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())

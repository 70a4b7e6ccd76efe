"""BENCH_7: out-of-core slab streaming vs whole-grid execution.

Measures the tentpole quantity of the ``"stream-from-host"`` subsystem
("Beyond 16GB", PAPERS.md): a grid forced past the device-memory budget
(``CASPER_SLAB_BUDGET``) streams through the device in overlap-carrying
slabs (``kernels.stream``), against the whole-grid in-core plan on the
same inputs.  Two comparisons per workload, both in ``BENCH_7.json``:

* **modeled host<->device traffic**
  (:func:`repro.kernels.stream.host_device_traffic`): the streamed path
  re-uploads every slab window each fused block — the overhead ratio
  over the whole-grid upload+download is analytic and exact, so the CI
  smoke pins its shape;
* **measured wallclock** of ``iters`` applications, slabbed (host
  staging, double-buffered) vs whole-grid (jitted scan), min-of-reps
  alternating timing (the BENCH_4/5/6 discipline).

Correctness rides along: every workload records f64 ``bit_identical``
between the slabbed and whole-grid results — the smoke asserts it, the
full matrix lives in tests/test_slabs.py.  ``iters`` is chosen with a
remainder (``iters = q*sweeps + r``, ``r > 0``) so the composed
remainder-plan path is what gets benchmarked, not just the easy case.
"""
from __future__ import annotations

import contextlib
import os
import time

import numpy as np
import jax
import jax.numpy as jnp
from jax import enable_x64

from repro.core import PAPER_STENCILS, advect2d
from repro.core import perfmodel as pm
from repro.core import plan as _plan
from repro.core import ref as cref
from repro.kernels import stream as kstream

BENCH7_SCHEMA = "casper-bench-7"
BENCH7_VERSION = 1

#: Forced budget = grid bytes // this: a handful of slabs per grid,
#: deep enough overlap traffic to be visible in the model columns.
BUDGET_DIVISOR = 4


@contextlib.contextmanager
def forced_budget(n_bytes: int):
    """Scope ``CASPER_SLAB_BUDGET`` (lowering *and* the remainder plans
    lowered mid-run consult it, so the whole run stays inside)."""
    old = os.environ.get(pm.SLAB_BUDGET_ENV)
    os.environ[pm.SLAB_BUDGET_ENV] = str(int(n_bytes))
    try:
        yield
    finally:
        if old is None:
            os.environ.pop(pm.SLAB_BUDGET_ENV, None)
        else:
            os.environ[pm.SLAB_BUDGET_ENV] = old


def _mintime(fns: dict, reps: int) -> dict:
    for fn in fns.values():
        fn()                                    # warm up / compile / lower
    best = {k: float("inf") for k in fns}
    for _ in range(reps):
        for k, fn in fns.items():
            t0 = time.perf_counter()
            fn()
            best[k] = min(best[k], time.perf_counter() - t0)
    return best


def _bench_one(spec, shape, iters: int, sweeps: int, reps: int,
               backend: str) -> dict:
    rng = np.random.default_rng(17)
    host = rng.standard_normal(shape)           # f64 host-resident grid
    grid_bytes = host.nbytes
    budget = grid_bytes // BUDGET_DIVISOR

    with enable_x64():
        g = jnp.asarray(host, jnp.float64)
        whole = _plan.lower(spec, shape, jnp.float64, backend=backend,
                            sweeps=sweeps)
        out_whole = np.asarray(_plan.run_plan(whole, g, iters))

        with forced_budget(budget):
            slabbed = _plan.lower(spec, shape, jnp.float64, backend=backend,
                                  sweeps=sweeps)
            assert slabbed.streams_from_host, slabbed.ghost_strategy
            out_slab = np.asarray(_plan.run_plan(slabbed, host, iters))

            best = _mintime(
                {"slabbed": lambda: _plan.run_plan(slabbed, host, iters),
                 "whole": lambda: jax.block_until_ready(
                     _plan.run_plan(whole, g, iters))},
                reps=reps)

    traffic = kstream.host_device_traffic(slabbed, iters)
    return {
        "spec": spec.name,
        "boundary": spec.boundary_mode,
        "shape": list(shape),
        "iters": iters,
        "sweeps": sweeps,
        "budget_bytes": budget,
        "grid_bytes": grid_bytes,
        "n_slabs": len(slabbed.slabs),
        "slab_overlap": slabbed.slab_overlap,
        "traffic": traffic,
        "wallclock": {
            "slabbed_s": best["slabbed"],
            "whole_s": best["whole"],
            "ratio": best["slabbed"] / best["whole"],
        },
        "bit_identical": bool(np.array_equal(out_slab, out_whole)),
    }


def slabs_bench(reps: int = 3, shape=(192, 256), iters: int = 5,
                sweeps: int = 2, backend: str = "ref"):
    """Slabbed-vs-whole-grid on one zero-boundary and one periodic
    workload (both host-gather ghost paths).  Returns the standard
    ``(rows, detail)`` bench pair; ``detail`` keys: ``bench7`` (the
    ``BENCH_7.json`` payload) and ``summary``."""
    workloads = [
        _bench_one(PAPER_STENCILS["jacobi2d"], shape, iters, sweeps, reps,
                   backend),
        _bench_one(advect2d(), shape, iters, sweeps, reps, backend),
    ]
    payload = {
        "schema": BENCH7_SCHEMA,
        "version": BENCH7_VERSION,
        "config": {
            "backend": backend, "reps": reps, "iters": iters,
            "sweeps": sweeps, "shape": list(shape),
            "budget_divisor": BUDGET_DIVISOR,
            "jax_backend": jax.default_backend(),
        },
        "workloads": workloads,
    }
    rows = []
    for w in workloads:
        rows.append((f"slab_{w['spec']}_wallclock_ratio",
                     w["wallclock"]["slabbed_s"] * 1e6 / iters,
                     round(w["wallclock"]["ratio"], 2)))
        rows.append((f"slab_{w['spec']}_traffic_overhead", 0.0,
                     round(w["traffic"]["overhead"], 3)))
    detail = {
        "bench7": payload,
        "summary": {
            "mean_wallclock_ratio": float(np.mean(
                [w["wallclock"]["ratio"] for w in workloads])),
            "mean_traffic_overhead": float(np.mean(
                [w["traffic"]["overhead"] for w in workloads])),
            "all_bit_identical": all(w["bit_identical"] for w in workloads),
        },
    }
    return rows, detail


def bench7_schema_errors(payload) -> list[str]:
    """Validate a BENCH_7.json payload; returns a list of problems
    (empty = schema-valid)."""
    errs = []
    if not isinstance(payload, dict):
        return ["payload is not an object"]
    if payload.get("schema") != BENCH7_SCHEMA:
        errs.append(f"schema != {BENCH7_SCHEMA!r}")
    if not isinstance(payload.get("version"), int):
        errs.append("version missing/not int")
    if not isinstance(payload.get("config"), dict):
        errs.append("config missing")
    wls = payload.get("workloads")
    if not isinstance(wls, list) or not wls:
        return errs + ["workloads missing/empty"]
    for i, w in enumerate(wls):
        if not isinstance(w, dict):
            errs.append(f"workloads[{i}] not an object")
            continue
        for key in ("spec", "shape", "iters", "sweeps", "budget_bytes",
                    "grid_bytes"):
            if key not in w:
                errs.append(f"workloads[{i}].{key} missing")
        if not (isinstance(w.get("n_slabs"), int) and w.get("n_slabs", 0) > 0):
            errs.append(f"workloads[{i}].n_slabs not a positive int")
        traffic = w.get("traffic")
        if not isinstance(traffic, dict):
            errs.append(f"workloads[{i}].traffic missing")
        else:
            for key in ("slab_h2d_bytes", "slab_d2h_bytes",
                        "whole_h2d_bytes", "whole_d2h_bytes", "overhead"):
                if not isinstance(traffic.get(key), (int, float)):
                    errs.append(f"workloads[{i}].traffic.{key} not a number")
        wc = w.get("wallclock")
        if not isinstance(wc, dict):
            errs.append(f"workloads[{i}].wallclock missing")
        else:
            for key in ("slabbed_s", "whole_s", "ratio"):
                if not isinstance(wc.get(key), (int, float)):
                    errs.append(
                        f"workloads[{i}].wallclock.{key} not a number")
        if not isinstance(w.get("bit_identical"), bool):
            errs.append(f"workloads[{i}].bit_identical not a bool")
    return errs

"""BENCH_8: continuous-batching serving under open-loop Poisson load.

BENCH_5 measures the one-shot batched call against per-request
sequential dispatch on a static request list.  This bench measures what
the ROADMAP's serving scenario actually needs: a **long-lived**
:class:`repro.serve.scheduler.AsyncStencilServer` under an open-loop
Poisson arrival process (arrivals never wait on the server, so
saturation shows up as queue latency — not silently throttled offered
load), swept across three offered-load points over BENCH_5's mixed
spec/shape/iters workload:

* ``low``       — well under the sequential baseline's capacity: the
  server idles between arrivals; the smoke gate pins **zero deadline
  misses** here;
* ``mid``       — past sequential capacity: batching must be carrying
  the load;
* ``saturated`` — arrivals far faster than sequential capacity: the
  acceptance gate pins sustained throughput **>= 1.5x** the sequential
  baseline, with p50/p95/p99 reported per load point.

A separate **f64 leg** reruns the mix in double precision under
``enable_x64`` (the worker thread opts in via ``ServeConfig.x64`` — the
jax x64 context is thread-local) and asserts the served results are
**bit-identical** to ``serve_sequential`` on the same request multiset —
the correctness half of the acceptance criterion.  (The throughput sweep
itself runs BENCH_5's f32 precision: serving throughput is a
dispatch-amortization story, and f64 doubles compute per request without
touching dispatch cost.)
"""
from __future__ import annotations

import numpy as np
import jax
from jax import enable_x64

from repro.serve import (AsyncStencilServer, ServeConfig, StencilServer,
                         mixed_requests, poisson_workload, submit_open_loop)

BENCH8_SCHEMA = "casper-bench-8"
BENCH8_VERSION = 1

#: Offered load per sweep point, as multiples of the measured sequential
#: baseline throughput.
LOAD_MULTIPLIERS = {"low": 0.3, "mid": 1.5, "saturated": 6.0}


def _run_load_point(requests, seq_results, rate_rps: float, *,
                    sweeps: int, deadline_s: float, max_bucket_size: int,
                    seed: int, x64: bool = False) -> dict:
    config = ServeConfig.auto(rate_rps, max_bucket_size=max_bucket_size,
                              deadline_s=deadline_s, x64=x64)
    server = AsyncStencilServer(config=config, backend="ref",
                                sweeps=sweeps)
    with server:
        handles = submit_open_loop(
            server, poisson_workload(requests, rate_rps, seed=seed))
        server.drain()
    stats = server.stats()
    results = [h.result() for h in handles]
    return {
        "offered_rps": rate_rps,
        "sustained_rps": stats.requests_per_s,
        "makespan_s": stats.seconds,
        "max_wait_s": config.max_wait_s,
        "n_completed": stats.n_requests - stats.n_rejected - stats.n_shed,
        "n_deadline_missed": stats.n_deadline_missed,
        "n_shed": stats.n_shed,
        "n_buckets": stats.n_buckets,
        "latency_s": stats.latency_s,
        "close_reasons": stats.close_reasons,
        "bit_identical_to_sequential": bool(all(
            np.array_equal(a, b) for a, b in zip(results, seq_results))),
    }


def serving_load_bench(n: int = 384, seed: int = 11, sweeps: int = 4,
                       deadline_s: float = 10.0,
                       max_bucket_size: int = 64, n_f64: int = 48):
    """Open-loop Poisson load sweep over BENCH_5's serving mix, plus the
    f64 bit-identity leg.

    Returns the standard ``(rows, detail)`` bench pair; ``detail`` keys:
    ``bench8`` (the ``BENCH_8.json`` payload) and ``summary``.
    """
    requests = mixed_requests(n, seed=seed)
    front = StencilServer(backend="ref", sweeps=sweeps)
    # cold passes populate the plan/runner caches
    seq_results, _ = front.serve_sequential(requests)
    front.serve(requests)
    _, probe_stats = front.serve_sequential(requests)
    probe_rps = probe_stats.requests_per_s

    # warm the continuous path: compile the donated vmapped runner for
    # every (bucket key, size tier) the padded scheduler can dispatch —
    # compile time belongs to warm-up, not to the measured sweep
    warm = AsyncStencilServer(
        config=ServeConfig.auto(probe_rps,
                                max_bucket_size=max_bucket_size,
                                deadline_s=deadline_s),
        backend="ref", sweeps=sweeps)
    n_warmed = warm.warmup(requests)

    def saturated_point():
        return _run_load_point(
            requests, seq_results,
            LOAD_MULTIPLIERS["saturated"] * probe_rps, sweeps=sweeps,
            deadline_s=deadline_s, max_bucket_size=max_bucket_size,
            seed=seed + 1)

    # min-of-reps with ALTERNATING legs (the BENCH_5 discipline): the
    # gated ratio compares the sequential baseline against the saturated
    # continuous server, so both legs must sample the same slice of a
    # shared CI box — measuring them seconds apart lets a frequency or
    # load shift land on one leg only
    seq_reps, oneshot_reps, sat_reps = [], [], []
    for _ in range(3):
        seq_reps.append(front.serve_sequential(requests)[1])
        oneshot_reps.append(front.serve(requests)[1])
        sat_reps.append(saturated_point())
    seq_stats = max(seq_reps, key=lambda s: s.requests_per_s)
    batched_stats = max(oneshot_reps, key=lambda s: s.requests_per_s)
    seq_rps = seq_stats.requests_per_s
    batched_rps = batched_stats.requests_per_s

    load_points = []
    for label, mult in LOAD_MULTIPLIERS.items():
        if label == "saturated":
            point = max(sat_reps, key=lambda p: p["sustained_rps"])
        else:
            point = _run_load_point(requests, seq_results,
                                    mult * probe_rps, sweeps=sweeps,
                                    deadline_s=deadline_s,
                                    max_bucket_size=max_bucket_size,
                                    seed=seed + 1)
        point["label"] = label
        load_points.append(point)

    # the f64 leg: same mix in double precision; bit-identity against
    # serve_sequential is the correctness acceptance criterion
    requests64 = mixed_requests(n_f64, seed=seed, dtype=np.float64)
    with enable_x64():
        seq64_results, _ = front.serve_sequential(requests64)
        _, seq64_stats = front.serve_sequential(requests64)
    warm64 = AsyncStencilServer(
        config=ServeConfig.auto(seq64_stats.requests_per_s, x64=True,
                                max_bucket_size=max_bucket_size,
                                deadline_s=deadline_s),
        backend="ref", sweeps=sweeps)
    warm64.warmup(requests64)
    f64_point = _run_load_point(
        requests64, seq64_results,
        LOAD_MULTIPLIERS["mid"] * seq64_stats.requests_per_s,
        sweeps=sweeps, deadline_s=deadline_s,
        max_bucket_size=max_bucket_size, seed=seed + 2, x64=True)
    f64_check = {
        "n_requests": n_f64,
        "offered_rps": f64_point["offered_rps"],
        "sustained_rps": f64_point["sustained_rps"],
        "sequential_rps": seq64_stats.requests_per_s,
        "bit_identical_to_sequential":
            f64_point["bit_identical_to_sequential"],
        "n_deadline_missed": f64_point["n_deadline_missed"],
    }

    saturated = load_points[-1]
    low = load_points[0]
    ratio = saturated["sustained_rps"] / seq_rps
    payload = {
        "schema": BENCH8_SCHEMA,
        "version": BENCH8_VERSION,
        "config": {
            "backend": front.backend, "sweeps": sweeps,
            "n_requests": n, "seed": seed,
            "deadline_s": deadline_s,
            "max_bucket_size": max_bucket_size,
            "n_warmed_runners": n_warmed,
            "jax_backend": jax.default_backend(),
        },
        "baselines": {
            "sequential_rps": seq_rps,
            "batched_oneshot_rps": batched_rps,
            "sequential_s": seq_stats.seconds,
            "batched_oneshot_s": batched_stats.seconds,
        },
        "load_points": load_points,
        "f64_check": f64_check,
        "results": {
            "saturated_vs_sequential": ratio,
            "bit_identical_to_sequential": bool(
                f64_check["bit_identical_to_sequential"]
                and all(p["bit_identical_to_sequential"]
                        for p in load_points)),
            "low_load_deadline_misses": low["n_deadline_missed"],
            "saturated_p99_s": saturated["latency_s"]["p99"],
        },
    }
    rows = [
        ("serve_load_sequential_rps", 0.0, round(seq_rps, 1)),
        ("serve_load_saturated_sustained_rps", 0.0,
         round(saturated["sustained_rps"], 1)),
        ("serve_load_saturated_vs_sequential", 0.0, round(ratio, 2)),
        ("serve_load_saturated_p99_ms", 0.0,
         round(saturated["latency_s"]["p99"] * 1e3, 2)),
    ]
    detail = {
        "bench8": payload,
        "summary": {
            "saturated_vs_sequential": ratio,
            "sustained_rps": {p["label"]: round(p["sustained_rps"], 1)
                              for p in load_points},
            "p99_ms": {p["label"]: round(p["latency_s"]["p99"] * 1e3, 2)
                       for p in load_points},
            "bit_identical": payload["results"][
                "bit_identical_to_sequential"],
            "f64_bit_identical": f64_check["bit_identical_to_sequential"],
            "low_load_deadline_misses": low["n_deadline_missed"],
        },
    }
    return rows, detail


def bench8_schema_errors(payload) -> list[str]:
    """Validate a BENCH_8.json payload; returns a list of problems
    (empty = schema-valid).  Pinned so future PRs appending to the perf
    trajectory keep the file machine-readable."""
    errs = []
    if not isinstance(payload, dict):
        return ["payload is not an object"]
    if payload.get("schema") != BENCH8_SCHEMA:
        errs.append(f"schema != {BENCH8_SCHEMA!r}")
    if not isinstance(payload.get("version"), int):
        errs.append("version missing/not int")
    if not isinstance(payload.get("config"), dict):
        errs.append("config missing")
    base = payload.get("baselines")
    if not isinstance(base, dict):
        errs.append("baselines missing")
    else:
        for key in ("sequential_rps", "batched_oneshot_rps"):
            if not isinstance(base.get(key), (int, float)):
                errs.append(f"baselines.{key} not a number")
    points = payload.get("load_points")
    if not isinstance(points, list) or not points:
        errs.append("load_points missing/empty")
        points = []
    labels = [p.get("label") for p in points if isinstance(p, dict)]
    if set(labels) != set(LOAD_MULTIPLIERS):
        errs.append(f"load_points labels {labels} != "
                    f"{sorted(LOAD_MULTIPLIERS)}")
    for p in points:
        if not isinstance(p, dict):
            errs.append("load_point not an object")
            continue
        label = p.get("label", "?")
        for key in ("offered_rps", "sustained_rps", "makespan_s"):
            if not isinstance(p.get(key), (int, float)):
                errs.append(f"load_points[{label}].{key} not a number")
        for key in ("n_deadline_missed", "n_shed", "n_buckets"):
            if not isinstance(p.get(key), int):
                errs.append(f"load_points[{label}].{key} not an int")
        lat = p.get("latency_s")
        if not isinstance(lat, dict):
            errs.append(f"load_points[{label}].latency_s missing")
        else:
            for key in ("p50", "p95", "p99", "max", "mean"):
                if not isinstance(lat.get(key), (int, float)):
                    errs.append(f"load_points[{label}].latency_s.{key} "
                                f"not a number")
        if not isinstance(p.get("close_reasons"), dict):
            errs.append(f"load_points[{label}].close_reasons missing")
        if not isinstance(p.get("bit_identical_to_sequential"), bool):
            errs.append(f"load_points[{label}]"
                        f".bit_identical_to_sequential not a bool")
    f64 = payload.get("f64_check")
    if not isinstance(f64, dict):
        errs.append("f64_check missing")
    else:
        if not isinstance(f64.get("bit_identical_to_sequential"), bool):
            errs.append("f64_check.bit_identical_to_sequential not a bool")
        for key in ("sustained_rps", "sequential_rps"):
            if not isinstance(f64.get(key), (int, float)):
                errs.append(f"f64_check.{key} not a number")
    res = payload.get("results")
    if not isinstance(res, dict):
        return errs + ["results missing"]
    for key in ("saturated_vs_sequential", "saturated_p99_s"):
        if not isinstance(res.get(key), (int, float)):
            errs.append(f"results.{key} not a number")
    if not isinstance(res.get("bit_identical_to_sequential"), bool):
        errs.append("results.bit_identical_to_sequential not a bool")
    if not isinstance(res.get("low_load_deadline_misses"), int):
        errs.append("results.low_load_deadline_misses not an int")
    return errs

"""Continuous-batching stencil serving: admission queue, SLO deadlines,
overlapped host staging.

The one-shot :class:`~repro.serve.stencil.StencilServer` takes a static
request list and blocks on every transfer→compute→transfer chain in
sequence.  This module is the long-lived v2 around the same plan
pipeline:

1. **Admission** — :meth:`AsyncStencilServer.submit` validates each
   request (same structured :class:`~repro.serve.stencil.RequestError`
   contract as the one-shot server), applies backpressure past the
   ``queue_depth`` high-water mark (``shed_policy="reject"`` sheds the
   newest arrival — open-loop clients must never block), and appends
   admitted requests to the *open bucket* for their plan-cache key +
   ``iters``.
2. **Bucket close** — a bucket closes when it reaches
   ``max_bucket_size`` (reason ``"full"``), when its oldest request has
   waited ``max_wait_s`` (reason ``"timeout"``; the knob
   :func:`repro.core.perfmodel.bucket_close_wait_s` models), or when the
   server drains (reason ``"drain"``).
3. **Overlapped staging** — the worker runs a two-deep pipeline over
   :class:`repro.core.plan.BatchHandle`: stage bucket ``k+1`` (async
   host→device upload) and *dispatch* its vmapped fused call (async,
   donated staging buffer) while bucket ``k`` is still computing; only
   then block on ``k``'s fetch.  The same upload/compute/download
   overlap the slab-streaming executor (:mod:`repro.kernels.stream`)
   proves per slab, applied per bucket.
4. **Completion** — each request's :class:`RequestHandle` resolves with
   its result, submit→complete latency, and deadline verdict;
   :meth:`AsyncStencilServer.stats` aggregates p50/p95/p99 latency,
   shed/reject/deadline-miss counts, close reasons, and the plan-cache
   delta into the same :class:`~repro.serve.stencil.ServeStats`.

Configs are proven before the server starts:
:func:`repro.analysis.check_serve_config` runs at construction — error
findings raise, warnings warn (the serving analogue of the plan
verifier).
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
import warnings
from typing import Any, Mapping, Sequence

import numpy as np

from jax import enable_x64

from repro.core import perfmodel as _pm
from repro.core import plan as _plan
from repro.core.stencil import StencilPipeline, StencilSpec
from .stencil import (RequestError, ServeStats, StencilRequest,
                      StencilServer, _cache_delta, _throughput)


@dataclasses.dataclass(frozen=True)
class ServeConfig:
    """The continuous-batching knobs (see docs/serving.md for the full
    table).  Validated by :func:`repro.analysis.check_serve_config` at
    server construction: error findings raise ``ValueError``."""

    max_bucket_size: int = 32       # close a bucket "full" at this size
    max_wait_s: float = 0.005       # close "timeout" after the oldest
                                    # request waited this long
    queue_depth: int = 1024         # admission high-water mark: shed
                                    # arrivals past this many pending
    default_deadline_s: float | None = None
                                    # per-request SLO unless overridden
                                    # at submit; None = no deadline
    shed_policy: str = "reject"     # what to do past the high-water mark
    pad_buckets: bool = True        # pad each bucket to the next
                                    # power-of-two tier (vmap rows are
                                    # independent, so padding never
                                    # changes results): the jitted
                                    # runner retraces per batch *size*,
                                    # and tiers bound the compiled
                                    # shapes to log2(max_bucket_size)+1
                                    # per bucket key — warmable ahead of
                                    # traffic via ``warmup``
    x64: bool = False               # run the worker under jax x64 (the
                                    # enable_x64 context is thread-local,
                                    # so the worker must opt in itself)

    @classmethod
    def auto(cls, offered_rate_rps: float, *, max_bucket_size: int = 32,
             deadline_s: float | None = None, queue_depth: int = 1024,
             x64: bool = False) -> "ServeConfig":
        """Derive ``max_wait_s`` from the offered load via the perfmodel
        bucket-close heuristic (:func:`repro.core.perfmodel
        .bucket_close_wait_s`): wait long enough to amortize dispatch
        overhead, never longer than the bucket takes to fill or half the
        SLO budget."""
        wait = _pm.bucket_close_wait_s(offered_rate_rps, max_bucket_size,
                                       deadline_s=deadline_s)
        return cls(max_bucket_size=max_bucket_size, max_wait_s=wait,
                   queue_depth=queue_depth, default_deadline_s=deadline_s,
                   x64=x64)


def bucket_tiers(max_bucket_size: int) -> tuple[int, ...]:
    """The padded batch sizes a server with this bucket cap dispatches:
    powers of two up to the cap, plus the cap itself."""
    tiers = []
    t = 1
    while t < max_bucket_size:
        tiers.append(t)
        t *= 2
    tiers.append(max_bucket_size)
    return tuple(tiers)


def _pad_tier(n: int, max_bucket_size: int) -> int:
    """The smallest tier >= ``n``."""
    for t in bucket_tiers(max_bucket_size):
        if t >= n:
            return t
    return max_bucket_size


class RequestRejected(RuntimeError):
    """Raised by :meth:`RequestHandle.result` when the request was
    rejected (validation failure, shed under backpressure, or an
    internal execution error).  Carries the structured
    :class:`~repro.serve.stencil.RequestError`."""

    def __init__(self, error: RequestError):
        super().__init__(f"{error.error}: {error.message}")
        self.error = error


class RequestHandle:
    """One submitted request's future: resolves to a host result array
    or a structured :class:`~repro.serve.stencil.RequestError`."""

    def __init__(self, request: StencilRequest,
                 deadline_s: float | None):
        self.request = request
        self.deadline_s = deadline_s
        self._event = threading.Event()
        self._result: np.ndarray | None = None
        self._error: RequestError | None = None
        self._submit_t: float = 0.0
        self._latency_s: float | None = None
        self._deadline_missed = False

    def done(self) -> bool:
        return self._event.is_set()

    def wait(self, timeout: float | None = None) -> bool:
        return self._event.wait(timeout)

    def result(self, timeout: float | None = None) -> np.ndarray:
        """Block until the request completes; the host result array, or
        :class:`RequestRejected` when it was rejected/shed/failed."""
        if not self._event.wait(timeout):
            raise TimeoutError(
                f"request {self.request.spec_name!r} not complete after "
                f"{timeout}s")
        if self._error is not None:
            raise RequestRejected(self._error)
        assert self._result is not None
        return self._result

    @property
    def error(self) -> RequestError | None:
        """The structured rejection, if any (``None`` while pending or
        when the request completed normally)."""
        return self._error

    @property
    def latency_s(self) -> float | None:
        """Submit→complete latency (``None`` until done, and for
        requests rejected at admission, which never entered the
        queue)."""
        return self._latency_s

    @property
    def deadline_missed(self) -> bool:
        return self._deadline_missed

    # internal completion paths (scheduler only) ----------------------------
    def _reject(self, error: RequestError) -> None:
        self._error = error
        self._event.set()

    def _complete(self, result: np.ndarray, latency_s: float) -> None:
        self._result = result
        self._latency_s = latency_s
        if self.deadline_s is not None and latency_s > self.deadline_s:
            self._deadline_missed = True
        self._event.set()


@dataclasses.dataclass
class _Bucket:
    """One forming/closed batch: same plan-cache key + iters, executed
    as one vmapped fused call."""

    key: tuple
    spec: StencilSpec | StencilPipeline
    iters: int
    opened_t: float
    handles: list[RequestHandle] = dataclasses.field(default_factory=list)
    close_reason: str = ""


class AsyncStencilServer:
    """Long-lived continuous-batching server over the plan pipeline.

    Composes the one-shot :class:`~repro.serve.stencil.StencilServer`
    for specs/validation/bucket keys; adds the admission queue, the
    close timers, the SLO accounting, and the double-buffered worker.

    Use as a context manager (``with AsyncStencilServer(...) as srv:``)
    or call :meth:`start` / :meth:`stop` explicitly.  Requests may be
    submitted before :meth:`start` — they queue and execute once the
    worker runs (handy for deterministic tests).
    """

    def __init__(self,
                 specs: Mapping[str, StencilSpec | StencilPipeline]
                 | None = None, *,
                 config: ServeConfig | None = None,
                 backend: str = "ref", sweeps: int = 1,
                 tile: Any = None, interpret: bool | None = None):
        from repro import analysis as _analysis
        self.config = config or ServeConfig()
        findings = _analysis.check_serve_config(self.config)
        errors = [f for f in findings if f.severity == "error"]
        if errors:
            raise ValueError("invalid ServeConfig: "
                             + "; ".join(f.message for f in errors))
        for f in findings:
            if f.severity == "warning":
                warnings.warn(f"ServeConfig: {f.message}", stacklevel=2)
        self._front = StencilServer(specs, backend=backend, sweeps=sweeps,
                                    tile=tile, interpret=interpret)
        self._cond = threading.Condition()
        self._key_memo: dict[tuple, tuple] = {}
        self._open: dict[tuple, _Bucket] = {}
        self._ready: collections.deque[_Bucket] = collections.deque()
        self._thread: threading.Thread | None = None
        self._stopping = False
        self._worker_error: BaseException | None = None
        # window accounting (since construction), all under self._cond
        self._cache_before = _plan.plan_cache_stats()
        self._n_submitted = 0
        self._n_completed = 0
        self._n_pending = 0
        self._n_rejected = 0
        self._n_shed = 0
        self._n_deadline_missed = 0
        self._n_slab_streamed = 0
        self._points = 0
        self._latencies: list[float] = []
        self._bucket_stats: list[dict] = []
        self._close_reasons = {"full": 0, "timeout": 0, "drain": 0}
        self._first_submit_t: float | None = None
        self._last_complete_t: float | None = None

    # -- registry passthrough -----------------------------------------------
    @property
    def specs(self) -> dict[str, StencilSpec | StencilPipeline]:
        return self._front.specs

    def register(self, spec: StencilSpec | StencilPipeline) -> None:
        with self._cond:
            self._front.register(spec)
            self._key_memo.clear()      # the name may now mean a new spec

    # -- lifecycle ----------------------------------------------------------
    def start(self) -> "AsyncStencilServer":
        """Start the worker thread (idempotent)."""
        with self._cond:
            if self._stopping:
                raise RuntimeError("server already stopped")
            if self._thread is None:
                self._thread = threading.Thread(
                    target=self._worker, name="casper-serve", daemon=True)
                self._thread.start()
        return self

    def stop(self) -> None:
        """Drain everything queued, then shut the worker down
        (idempotent).  Every admitted request completes before stop
        returns."""
        with self._cond:
            already = self._stopping
            self._stopping = True
            self._cond.notify_all()
        if not already and self._thread is None:
            # never started: start the worker so queued requests drain
            # through the normal pipeline before shutdown
            self._thread = threading.Thread(
                target=self._worker, name="casper-serve", daemon=True)
            self._thread.start()
        if self._thread is not None:
            self._thread.join()

    def drain(self) -> None:
        """Close every open bucket (reason ``"drain"``) and block until
        all admitted requests have completed.  The server keeps
        running."""
        with self._cond:
            if self._thread is None:
                raise RuntimeError("server not started")
            self._close_all_locked("drain")
            self._cond.notify_all()
            while self._n_pending > 0 and self._worker_error is None:
                self._cond.wait(timeout=0.1)

    def __enter__(self) -> "AsyncStencilServer":
        return self.start()

    def __exit__(self, *exc: object) -> None:
        self.stop()

    def warmup(self, requests: Sequence[StencilRequest]) -> int:
        """Pre-compile the donated vmapped runners for every bucket tier
        of every distinct bucket key in ``requests`` (and lower their
        plans), so live traffic never pays a compile: with
        ``pad_buckets`` the compiled batch shapes are exactly
        ``bucket_tiers(max_bucket_size)`` per key.  Returns the number
        of ``(bucket key, tier)`` combinations warmed.  Call before
        :meth:`start` (or any time from the submitting thread)."""
        ctx = enable_x64() if self.config.x64 else contextlib.nullcontext()
        exemplars: dict[tuple, StencilRequest] = {}
        for req in requests:
            if self._front.validate_request(req) is None:
                exemplars.setdefault(self._front.bucket_key(req), req)
        tiers = (bucket_tiers(self.config.max_bucket_size)
                 if self.config.pad_buckets
                 else range(1, self.config.max_bucket_size + 1))
        n = 0
        with ctx:
            for req in exemplars.values():
                shape = tuple(req.grid.shape)
                if self._slab_plan(self.specs[req.spec_name], shape,
                                   req.grid.dtype) is not None:
                    continue            # slab path: no vmapped runner
                bh = _plan.batch_handle(self.specs[req.spec_name],
                                        self._front.backend,
                                        self._front.sweeps,
                                        self._front.tile_request,
                                        self._front.interpret)
                for tier in tiers:
                    staged = bh.stage([req.grid] * tier)
                    bh.fetch(bh.dispatch(staged, int(req.iters)))
                    n += 1
        return n

    # -- admission ----------------------------------------------------------
    def submit(self, request: StencilRequest, *,
               deadline_s: float | None = None) -> RequestHandle:
        """Admit one request; never raises for a *bad request* — the
        returned handle resolves immediately with a structured
        :class:`~repro.serve.stencil.RequestError` on validation failure
        or backpressure shed.  ``deadline_s`` overrides the config
        default SLO for this request."""
        if deadline_s is None:
            deadline_s = self.config.default_deadline_s
        handle = RequestHandle(request, deadline_s)
        err = self._front.validate_request(request)
        now = time.perf_counter()
        with self._cond:
            if self._stopping:
                raise RuntimeError("server stopped")
            self._n_submitted += 1
            if self._first_submit_t is None:
                self._first_submit_t = now
            if err is not None:
                self._n_rejected += 1
                handle._reject(err)
                return handle
            if self._n_pending >= self.config.queue_depth:
                self._n_shed += 1
                handle._reject(RequestError(
                    request.spec_name, "shed",
                    f"queue past high-water mark "
                    f"({self._n_pending} pending >= queue_depth "
                    f"{self.config.queue_depth})"))
                return handle
            handle._submit_t = now
            self._n_pending += 1
            # memoized bucket_key: the admission path runs per request
            # at tens of kilohertz, and plan_key re-derivation is the
            # single most expensive step on it
            memo = (request.spec_name, request.grid.shape,
                    request.grid.dtype, int(request.iters))
            key = self._key_memo.get(memo)
            if key is None:
                key = self._key_memo[memo] = self._front.bucket_key(request)
            bucket = self._open.get(key)
            wake = False
            if bucket is None:
                bucket = _Bucket(key=key,
                                 spec=self.specs[request.spec_name],
                                 iters=int(request.iters), opened_t=now)
                self._open[key] = bucket
                wake = True             # worker must (re-)arm its timer
            bucket.handles.append(handle)
            if len(bucket.handles) >= self.config.max_bucket_size:
                self._close_locked(bucket, "full")
                wake = True             # a bucket is ready to execute
            if wake:
                self._cond.notify_all()
        return handle

    # -- bucket lifecycle (all under self._cond) ----------------------------
    def _close_locked(self, bucket: _Bucket, reason: str) -> None:
        bucket.close_reason = reason
        self._close_reasons[reason] += 1
        del self._open[bucket.key]
        self._ready.append(bucket)

    def _close_expired_locked(self, now: float) -> None:
        for bucket in list(self._open.values()):
            if now - bucket.opened_t >= self.config.max_wait_s:
                self._close_locked(bucket, "timeout")

    def _close_all_locked(self, reason: str) -> None:
        for bucket in list(self._open.values()):
            self._close_locked(bucket, reason)

    def _next_bucket(self, block: bool) -> _Bucket | None:
        """Pop the next closed bucket.  Non-blocking when the worker has
        a bucket in flight (a miss means: go finish the in-flight one);
        blocking otherwise, with the wait capped at the earliest open
        bucket's close time so ``max_wait_s`` closes fire on schedule."""
        with self._cond:
            while True:
                self._close_expired_locked(time.perf_counter())
                if self._ready:
                    return self._ready.popleft()
                if not block:
                    return None
                if self._stopping:
                    if self._open:
                        self._close_all_locked("drain")
                        continue
                    return None
                timeout = None
                if self._open:
                    earliest = min(b.opened_t for b in self._open.values())
                    timeout = max(
                        earliest + self.config.max_wait_s
                        - time.perf_counter(), 0.0)
                self._cond.wait(timeout=timeout)

    # -- execution ----------------------------------------------------------
    def _worker(self) -> None:
        ctx = (enable_x64() if self.config.x64
               else contextlib.nullcontext())
        with ctx:
            self._pipeline()

    def _pipeline(self) -> None:
        """The two-deep staging pipeline: stage+dispatch bucket ``k+1``
        (both async) before blocking on bucket ``k``'s fetch, so
        ``k+1``'s host→device upload and queued compute overlap ``k``'s
        in-flight work — :mod:`repro.kernels.stream`'s slab pipeline,
        per bucket."""
        inflight: tuple[_Bucket, Any, float] | None = None
        while True:
            bucket = self._next_bucket(block=inflight is None)
            if bucket is None:
                if inflight is not None:
                    self._finish(*inflight)
                    inflight = None
                    continue
                break           # stopping, queue drained
            try:
                grids = [h.request.grid for h in bucket.handles]
                shape = tuple(grids[0].shape)
                dtype = grids[0].dtype
                if self._slab_plan(bucket.spec, shape, dtype) is not None:
                    if inflight is not None:
                        self._finish(*inflight)
                        inflight = None
                    self._run_slab(bucket, grids, shape, dtype)
                    continue
                bh = _plan.batch_handle(bucket.spec, self._front.backend,
                                        self._front.sweeps,
                                        self._front.tile_request,
                                        self._front.interpret)
                if self.config.pad_buckets:
                    tier = _pad_tier(len(grids),
                                     self.config.max_bucket_size)
                    grids = grids + [grids[0]] * (tier - len(grids))
                staged = bh.stage(grids)            # async upload
                t_dispatch = time.perf_counter()
                result = bh.dispatch(staged, bucket.iters)  # async compute
            except Exception as exc:                # noqa: BLE001
                if inflight is not None:
                    self._finish(*inflight)
                    inflight = None
                self._fail_bucket(bucket, exc)
                continue
            if inflight is not None:
                self._finish(*inflight)             # block on bucket k only
            inflight = (bucket, result, t_dispatch)
        if inflight is not None:
            self._finish(*inflight)

    def _slab_plan(self, spec, shape: tuple, dtype):
        """The lowered plan when buckets of this element shape must
        stream from the host (grids past the slab budget cannot stack on
        the device), else ``None`` — mirrors the one-shot server's
        out-of-core routing."""
        if not _plan._may_stream(spec, shape, dtype, self._front.backend):
            return None
        plan = _plan.lower(spec, shape, dtype,
                           backend=self._front.backend,
                           sweeps=self._front.sweeps,
                           tile=self._front.tile_request,
                           interpret=self._front.interpret)
        return plan if plan.needs_host_streaming else None

    def _run_slab(self, bucket: _Bucket, grids: list, shape: tuple,
                  dtype) -> None:
        plan = self._slab_plan(bucket.spec, shape, dtype)
        t0 = time.perf_counter()
        try:
            outs = [np.asarray(_plan.run_plan(plan, np.asarray(g),
                                              bucket.iters))
                    for g in grids]
        except Exception as exc:                    # noqa: BLE001
            self._fail_bucket(bucket, exc)
            return
        self._record(bucket, outs, shape, np.dtype(dtype),
                     time.perf_counter() - t0, slab=True)

    def _finish(self, bucket: _Bucket, result: Any,
                t_dispatch: float) -> None:
        try:
            out = np.asarray(result)                # device sync + download
        except Exception as exc:                    # noqa: BLE001
            self._fail_bucket(bucket, exc)
            return
        # drop the pad rows (vmap rows are independent: padding with
        # copies of row 0 never perturbs the real rows)
        self._record(bucket, list(out[:len(bucket.handles)]),
                     out.shape[1:], out.dtype,
                     time.perf_counter() - t_dispatch, slab=False)

    def _record(self, bucket: _Bucket, outs: list, shape, dtype,
                seconds: float, *, slab: bool) -> None:
        now = time.perf_counter()
        with self._cond:
            for handle, out in zip(bucket.handles, outs):
                handle._complete(out, now - handle._submit_t)
                if handle.deadline_missed:
                    self._n_deadline_missed += 1
                self._latencies.append(now - handle._submit_t)
                self._points += int(np.size(out))
            self._n_pending -= len(bucket.handles)
            self._n_completed += len(bucket.handles)
            if slab:
                self._n_slab_streamed += len(bucket.handles)
            self._last_complete_t = now
            self._bucket_stats.append({
                "spec": bucket.spec.name, "shape": tuple(shape),
                "dtype": np.dtype(dtype).name, "iters": bucket.iters,
                "size": len(bucket.handles), "seconds": seconds,
                "slab_streamed": slab,
                "close_reason": bucket.close_reason,
            })
            self._cond.notify_all()

    def _fail_bucket(self, bucket: _Bucket, exc: BaseException) -> None:
        with self._cond:
            self._worker_error = exc
            for handle in bucket.handles:
                handle._reject(RequestError(
                    handle.request.spec_name, "internal",
                    f"{type(exc).__name__}: {exc}"))
            self._n_pending -= len(bucket.handles)
            self._n_rejected += len(bucket.handles)
            self._cond.notify_all()

    # -- reporting ----------------------------------------------------------
    def stats(self) -> ServeStats:
        """The serving window so far (since construction) as
        :class:`~repro.serve.stencil.ServeStats`: sustained throughput
        over the first-submit→last-complete makespan, latency
        percentiles, shed/reject/deadline-miss counts, bucket-close
        reasons, and the plan-cache delta.  Bucket stats are sorted on
        the bucket identity so two runs of the same request multiset
        report identically regardless of arrival order."""
        with self._cond:
            latencies = list(self._latencies)
            if (self._first_submit_t is not None
                    and self._last_complete_t is not None):
                seconds = max(self._last_complete_t
                              - self._first_submit_t, 0.0)
            else:
                seconds = 0.0
            buckets = sorted(
                self._bucket_stats,
                key=lambda b: (b["spec"], b["shape"], b["dtype"],
                               b["iters"], b["size"], b["close_reason"]))
            stats = ServeStats(
                n_requests=self._n_submitted,
                n_buckets=len(buckets),
                seconds=seconds,
                requests_per_s=_throughput(self._n_completed, seconds),
                points_per_s=_throughput(self._points, seconds),
                batched=True,
                plan_cache=_cache_delta(self._cache_before,
                                        _plan.plan_cache_stats()),
                buckets=buckets,
                n_slab_streamed=self._n_slab_streamed,
                n_rejected=self._n_rejected,
                n_shed=self._n_shed,
                n_deadline_missed=self._n_deadline_missed,
                latency_s=_latency_summary(latencies),
                close_reasons=dict(self._close_reasons))
        return stats


def _latency_summary(latencies: Sequence[float]) -> dict | None:
    """p50/p95/p99/max/mean over per-request submit→complete
    latencies (``None`` when nothing completed)."""
    if not latencies:
        return None
    arr = np.asarray(latencies, dtype=np.float64)
    return {
        "p50": float(np.percentile(arr, 50)),
        "p95": float(np.percentile(arr, 95)),
        "p99": float(np.percentile(arr, 99)),
        "max": float(arr.max()),
        "mean": float(arr.mean()),
    }

"""Out-of-core slab streaming: the ``"stream-from-host"`` plan executor.

"Beyond 16GB: Out-of-Core Stencil Computations" (PAPERS.md) one level
above Casper's cache: a grid that exceeds the device-memory budget
(``perfmodel.slab_budget_bytes``, env ``CASPER_SLAB_BUDGET``) stays
host-resident and streams through the device in slabs along the
outermost axis.  The slab boundary is *just a halo against host
memory* — each uploaded window carries ``sweeps * halo`` ghost rows per
side gathered from the neighbouring host rows (PR 2's deep-halo
arithmetic verbatim), so the fused window executors compute every slab
unchanged — ``kernels.engine.stencil_window_sweep`` on ``pallas`` and
its core ``ref.masked_window_pipeline`` on ``ref``, one call each for a
spec or a pipeline — and the result stays f64 bit-identical to the
whole-grid oracle (pinned across the rank x boundary x sweeps x
structure matrix in tests/test_slabs.py).

Execution double-buffers: while slab ``k`` computes on device, slab
``k+1``'s window is gathered and uploaded behind it and slab ``k-1``'s
output downloads — the device buffers are donated (off-CPU), so the
streaming resident set is exactly ``perfmodel.slab_resident_bytes``.
The overlap rows are *redundantly recomputed* by both neighbouring
slabs, which is what lets ``iters = q*sweeps + r`` compose: every fused
block is a complete, exact pass over the grid, so ``q`` blocks plus one
remainder block chain bit-identically just like the in-core scan.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import plan as _plan
from repro.core import ref as _ref


def slab_window(host: np.ndarray, slab: tuple[int, int], overlap: int,
                plan) -> np.ndarray:
    """Gather one slab's input window from the host-resident grid.

    Outermost axis: rows ``[start - overlap, stop + overlap)`` by
    *global* coordinate — periodic wraps, reflect folds, zero/constant
    fills out-of-grid rows, and an overlap deeper than a slab simply
    gathers across several host slabs (the multi-slab analogue of PR 2's
    multi-hop exchange).  Dims 1.. are ghost-padded ``deep_halo[d]``
    wide by the plan's boundary mode, exactly what the masked window
    executors expect.
    """
    start, stop = slab
    n0 = host.shape[0]
    mode, value = plan.boundary_mode, plan.boundary_value
    idx = np.arange(start - overlap, stop + overlap)
    if mode == "periodic":
        rows = np.take(host, _ref.periodic_index(idx, n0), axis=0)
    elif mode == "reflect":
        rows = np.take(host, _ref.reflect_index(idx, n0), axis=0)
    else:
        rows = np.take(host, np.clip(idx, 0, n0 - 1), axis=0)
        inside = (idx >= 0) & (idx < n0)
        if not inside.all():
            fill = value if mode == "constant" else 0.0
            rows = rows.copy()
            rows[~inside] = np.asarray(fill, dtype=host.dtype)
    widths = (0,) + tuple(plan.deep_halo[1:])
    return _ref.pad_boundary_numpy(rows, widths, mode, value)


@functools.lru_cache(maxsize=512)
def _slab_fn(plan, slab_len: int):
    """Jitted fused compute for one slab of ``plan`` (donating its
    window buffer off-CPU).  Cached per ``(plan, slab_len)``: a streamed
    run traces at most twice — the equal-length slabs and the short
    remainder slab — with the slab's global origin passed as a traced
    operand so every slab reuses one compiled kernel."""
    out_shape = (slab_len,) + plan.shape[1:]
    spec = plan.spec
    donate = () if jax.default_backend() == "cpu" else (0,)

    @functools.partial(jax.jit, donate_argnums=donate)
    def run(window, start0):
        starts = [start0] + [0] * (len(plan.shape) - 1)
        if plan.backend in _plan.KERNEL_BACKENDS:
            from repro.kernels import engine as keng      # lazy: optional dep
            return keng.stencil_window_sweep(
                spec, window, out_shape, starts, plan.shape,
                tile=plan.tile, sweeps=plan.sweeps, interpret=plan.interpret)
        return _ref.masked_window_pipeline(
            window, plan.stages, out_shape, plan.sweeps, starts,
            plan.shape, window.dtype).astype(window.dtype)
    return run


def _upload(plan, host: np.ndarray, k: int):
    start, stop = plan.slabs[k]
    window = slab_window(host, (start, stop), plan.slab_overlap, plan)
    return jax.device_put(window), start


def _run_block(plan, host: np.ndarray) -> np.ndarray:
    """One fused block (``plan.sweeps`` applications) over every slab,
    with upload / compute / download overlap: slab ``k+1`` stages onto
    the device while slab ``k``'s (async-dispatched) compute runs, and
    slab ``k-1`` downloads only then — the jax async dispatch queue
    provides the overlap, the host loop just keeps one slab of
    lookahead in flight."""
    out = np.empty_like(host)
    staged = _upload(plan, host, 0)
    pending = None                             # (slab_index, device_result)
    for k in range(len(plan.slabs)):
        window, start = staged
        run = _slab_fn(plan, plan.slabs[k][1] - plan.slabs[k][0])
        result = run(window, jnp.asarray(start, jnp.int32))
        if k + 1 < len(plan.slabs):
            staged = _upload(plan, host, k + 1)
        if pending is not None:
            j, prev = pending
            out[slice(*plan.slabs[j])] = np.asarray(prev)
        pending = (k, result)
    j, prev = pending
    out[slice(*plan.slabs[j])] = np.asarray(prev)
    return out


def execute_plan(plan, grid) -> np.ndarray:
    """One fused block of a ``"stream-from-host"`` plan: the host-side
    twin of ``ref.execute_plan`` / ``kernels.engine.execute_plan``,
    returning the updated *host-resident* grid."""
    if not plan.streams_from_host:
        raise ValueError(
            f"not a slab-streamed plan: ghost={plan.ghost_strategy!r}")
    host = np.asarray(grid)
    if host.ndim == len(plan.shape) + 1:       # leading batch dim
        return np.stack([_run_block(plan, g) for g in host])
    if host.shape != plan.shape:
        raise ValueError(f"grid shape {host.shape} != plan shape "
                         f"{plan.shape}")
    return _run_block(plan, host)


def run_plan_streamed(plan, grid, iters: int) -> np.ndarray:
    """``iters`` total applications on the host-staging path: ``q`` full
    slab passes plus one remainder pass whose narrower plan comes from
    the cache — the eager twin of ``plan.run_plan``'s scan (device
    staging cannot be traced).  Also carries staged pipelines whose
    *stage* plans stream (``plan.needs_host_streaming``)."""
    host = np.asarray(grid)
    if host.ndim == len(plan.shape) + 1:       # leading batch dim
        return np.stack([run_plan_streamed(plan, g, iters) for g in host])
    q, r = plan.decompose(iters)
    if iters == 0:
        return host.copy()
    if not plan.streams_from_host:             # staged chain, streamed stages
        for _ in range(q):
            host = np.asarray(_plan.execute(plan, host))
        if r:
            host = np.asarray(_plan.execute(plan.remainder(r), host))
        return host
    for _ in range(q):
        host = _run_block(plan, host)
    if r:
        host = run_plan_streamed(plan.remainder(r), host, r)
    return host


def host_device_traffic(plan, iters: int | None = None) -> dict:
    """Modeled host<->device bytes of a streamed run vs the whole-grid
    baseline (BENCH_7's traffic columns).  Per fused block the streamed
    path uploads every slab's ghost-padded window and downloads the full
    grid; the whole-grid baseline uploads and downloads the grid once
    for the entire run.  ``iters=None`` models a single fused block."""
    itemsize = np.dtype(plan.dtype).itemsize
    grid_bytes = int(np.prod(plan.shape)) * itemsize
    window_bytes = 0
    for start, stop in plan.slabs:
        rows = (stop - start) + 2 * plan.slab_overlap
        per_row = itemsize
        for d in range(1, len(plan.shape)):
            per_row *= plan.shape[d] + 2 * plan.deep_halo[d]
        window_bytes += rows * per_row
    blocks = 1
    if iters is not None:
        q, r = plan.decompose(iters)
        blocks = q + (1 if r else 0)
    h2d = window_bytes * blocks
    d2h = grid_bytes * blocks
    return {
        "n_slabs": len(plan.slabs),
        "slab_overlap": plan.slab_overlap,
        "blocks": blocks,
        "slab_h2d_bytes": h2d,
        "slab_d2h_bytes": d2h,
        "whole_h2d_bytes": grid_bytes,
        "whole_d2h_bytes": grid_bytes,
        "overhead": (h2d + d2h) / (2 * grid_bytes),
    }

"""Distributed stencil execution: shard_map + deep halo exchange.

The TPU-cluster analogue of Casper's §4.2 data mapping: each device owns a
*contiguous block* of the grid (the "stencil segment" block -> "LLC slice"
assignment), computes its block locally at local-memory bandwidth, and only
exchanges the halo surface with neighboring devices over ICI
(`lax.ppermute`) — the analogue of Casper's remote-slice NoC accesses, which
occur only at block boundaries.

Temporal blocking extends the same trade across the wire: ``sweeps=t``
exchanges a ``t*halo``-deep halo *once* per ``t`` sweeps (one pair of
``ppermute`` launches per sharded axis instead of ``t`` pairs), then runs
all ``t`` applications locally on the window assembled once from the
shard and the received faces — the
communication-avoiding deep-halo scheme of out-of-core stencil work, at
device-shard granularity.  When a neighbor's block is narrower than the
deep halo, the exchange falls back to a multi-hop gather (``ppermute`` at
distances 1..k), so slivers and tiny shards stay correct.

This module is a thin *executor* of plans lowered by
:mod:`repro.core.plan`: ``distributed_stencil_fn`` lowers one
:class:`~repro.core.plan.ExecutionPlan` per (spec, global shape, dtype,
backend, sweeps, tile, mesh fingerprint) — through the process-wide plan
cache, so repeat meshes/shapes re-lower nothing — and
:func:`execute_plan` runs one fused step from it.  The boundary-mode →
exchange-strategy decision (wrap-ring / zero-fill / local edge-fixup)
and the shard-shape tile autotune now live in ``plan.lower``, not here:

* ``zero-fill`` falls out of `ppermute` semantics for free — devices
  without a source in the permutation receive zeros;
* ``wrap-ring`` (periodic) turns each hop into a wrap-around *ring*
  permutation (``(i, (i+j) mod n)`` for every device), so grid-edge
  devices receive the opposite edge of the grid instead of fill;
* ``edge-fixup`` (constant(c) / reflect) keeps the zero-filled exchange
  and then fixes the out-of-grid ghosts of the received faces up
  locally — a constant fill, or a mirror gather whose source provably
  lies in the shard's edge rows or the faces already received.

Between fused sweeps, the shard-local compute restores intermediates that
fall outside the *global* grid to the mode's boundary extension
(`ref.masked_window_pipeline`, the one fused core), matching the
oracle's re-pad-every-sweep semantics exactly — f64 bit-identically for
all four modes (see docs/boundaries.md).
"""
from __future__ import annotations

import functools
import math
from typing import Callable, Literal, Sequence

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from . import plan as _plan
from . import ref as _ref
from . import stencil as _stencil
from .stencil import StencilSpec


def _hop_widths(halo: int, size: int) -> list[int]:
    """Width of the piece neighbour ``j`` (1, 2, ...) contributes to a
    ``halo``-deep exchange of ``size``-long blocks: its edge nearest to
    us, whole blocks except (possibly) the farthest hop."""
    return [min(size, halo - j * size) for j in range(-(-halo // size))]


def _edge_rows(a: jax.Array, axis: int, halo: int, size: int) -> jax.Array:
    """The rows of ``a`` along ``axis`` that a ``halo``-deep exchange or
    boundary extension of a ``size``-long block reads: the whole block,
    or, when it is longer than ``2*(halo+1)``, its first and last
    ``halo + 1`` rows (the edges sent, and every ghost's wrap or mirror
    source)."""
    keep = halo + 1
    if size <= 2 * keep:
        return a
    return jnp.concatenate([lax.slice_in_dim(a, 0, keep, axis=axis),
                            lax.slice_in_dim(a, size - keep, size,
                                             axis=axis)], axis=axis)


def _nest(center: jax.Array, faces) -> jax.Array:
    """``center`` with each ``(dim, left, right)`` face pair, in order,
    concatenated around it along ``dim``."""
    for d, left, right in faces:
        center = jnp.concatenate([left, center, right], axis=d)
    return center


def exchange_halo_1axis(x: jax.Array, axis: int, halo: int,
                        axis_name: str, *, mode: str = "zero",
                        value: float = 0.0,
                        strategy: str | None = None,
                        size: int | None = None) -> jax.Array:
    """Pad dim ``axis`` of the local block with ``halo`` neighbor elements
    per side, serving grid edges per the exchange ``strategy``.

    Sends this block's right edge to the right neighbor (it becomes that
    neighbor's left halo) and vice versa.  ``halo`` may exceed the local
    block extent: the exchange then gathers from neighbors up to
    ``ceil(halo/size)`` hops away — one ``ppermute`` per hop per
    direction, the multi-hop fallback for deep halos on narrow shards.

    ``x`` is the block, or, given the block's extent ``size``, only its
    edge rows along ``axis`` (:func:`_edge_rows`): the exchange reads
    nothing else, so the mesh path sends and fixes up faces without
    touching the rest of the shard.

    ``strategy`` is one of :data:`repro.core.plan.EXCHANGE_STRATEGIES`
    (``zero-fill`` / ``wrap-ring`` / ``edge-fixup``); when ``None`` it is
    resolved from the boundary ``mode`` by the one decision function,
    :func:`repro.core.plan.exchange_strategy_for` — this module only
    executes the choice.  ``mode``/``value`` still parameterize the
    edge-fixup mechanics (constant fill vs reflect mirror).
    """
    if halo == 0:
        return x
    if strategy is None:
        strategy = _plan.exchange_strategy_for(mode)
    n = lax.psum(1, axis_name)  # static mesh size along the axis
    rows = x.shape[axis]
    size = rows if size is None else size
    from_left, from_right = [], []
    for j, w in enumerate(_hop_widths(halo, size), start=1):
        right_edge = lax.slice_in_dim(x, rows - w, rows, axis=axis)
        left_edge = lax.slice_in_dim(x, 0, w, axis=axis)
        if strategy == "wrap-ring":     # wrap-around ring, every device
            from_left.append(lax.ppermute(
                right_edge, axis_name,
                [(i, (i + j) % n) for i in range(n)]))
            from_right.append(lax.ppermute(
                left_edge, axis_name,
                [(i, (i - j) % n) for i in range(n)]))
            continue
        if j >= n:                      # no neighbor that far: grid edge
            from_left.append(jnp.zeros_like(right_edge))
            from_right.append(jnp.zeros_like(left_edge))
            continue
        from_left.append(lax.ppermute(
            right_edge, axis_name, [(i, i + j) for i in range(n - j)]))
        from_right.append(lax.ppermute(
            left_edge, axis_name, [(i, i - j) for i in range(j, n)]))
    # left halo runs farthest-to-nearest neighbor, right halo the reverse.
    left = jnp.concatenate(from_left[::-1], axis=axis)
    right = jnp.concatenate(from_right, axis=axis)
    if strategy == "edge-fixup":
        left, right = _fix_edge_ghosts_1axis(left, x, right, axis, halo,
                                             size, axis_name, n, mode,
                                             value)
    return jnp.concatenate([left, x, right], axis=axis)


def _fix_edge_ghosts_1axis(left: jax.Array, edge: jax.Array,
                           right: jax.Array, axis: int, halo: int,
                           size: int, axis_name: str, n,
                           mode: str, value: float):
    """Overwrite the out-of-grid coordinates of the received faces
    ``left`` and ``right`` of a ``size``-long block along ``axis`` with
    the ``constant`` fill or the ``reflect`` mirror of the block's own
    (already exchanged, hence globally correct) data.

    ``edge`` is the block or its edge rows (:func:`_edge_rows`).  A
    ghost ``k <= halo`` layers out mirrors a point at most ``k`` layers
    in, so each face is fixed within the ``2*halo + 1`` rows of
    ``[left, edge, right]`` that end at it.  Those rows run on without a
    gap from the face either way: the face, the block's nearest
    ``halo + 1`` rows and, on a block narrower than that, the far
    face."""
    start = lax.axis_index(axis_name) * size
    grid_n = n * size
    rows = jnp.concatenate([left, edge, right], axis=axis)
    ext = rows.shape[axis]
    m = min(ext, 2 * halo + 1)

    def fix(at, g0):
        part = lax.slice_in_dim(rows, at, at + m, axis=axis)
        if mode == "constant":
            g = g0 + jnp.arange(m, dtype=jnp.int32)
            shape = [1] * part.ndim
            shape[axis] = m
            inside = ((g >= 0) & (g < grid_n)).reshape(shape)
            return jnp.where(inside, part, jnp.asarray(value, part.dtype))
        return _ref.reflect_gather(part, axis, g0, grid_n, halo)

    lo = fix(0, start - halo)
    hi = fix(ext - m, start + size + halo - m)
    return (lax.slice_in_dim(lo, 0, halo, axis=axis),
            lax.slice_in_dim(hi, m - halo, m, axis=axis))


def exchange_tag(plan: "_plan.ExecutionPlan", block: Sequence[int],
                 itemsize: int, window: Sequence[int]) -> dict[str, str]:
    """The mesh path's fields of the shard-local kernel's tag
    (``kernels.engine._kernel_tag``): ``shards``, the mesh's extent
    along each sharded grid dim (``"2x2"``); ``exchange_bytes``, the
    bytes a ``block``-shaped shard receives from its neighbours in one
    fused block's exchange (:func:`_local_multisweep`'s order, so an
    axis exchanged later carries the corners of those before it; every
    hop counted; no bytes where a grid edge has no sender; the mean
    over the shards where the mesh's edges make them differ); and
    ``window_bytes``, the bytes of the one ``window``-shaped array the
    block assembles for the kernel."""
    shape = list(block)
    received = 0.0
    shards = []
    for d, name in enumerate(plan.grid_axes):
        if name is not None:
            n = plan.mesh.shape[name]
            shards.append(str(n))
            across = math.prod(shape) // shape[d]
            for j, w in enumerate(_hop_widths(plan.deep_halo[d], shape[d]),
                                  start=1):
                senders = (n if plan.exchange[d] == "wrap-ring"
                           else max(n - j, 0))
                received += 2 * w * across * senders / n
        shape[d] += 2 * plan.deep_halo[d]
    return {"shards": "x".join(shards),
            "exchange_bytes": str(round(received * itemsize)),
            "window_bytes": str(math.prod(window) * itemsize)}


def _axis_name(plan: "_plan.ExecutionPlan", d: int) -> str | None:
    return plan.grid_axes[d] if d < len(plan.grid_axes) else None


def _fill(plan: "_plan.ExecutionPlan"):
    """The constant an unsharded dim's boundary ghosts hold, or ``None``
    where the mode copies them from the grid (periodic, reflect)."""
    return {"zero": 0.0, "constant": plan.boundary_value}.get(
        plan.boundary_mode)


def _faces(plan: "_plan.ExecutionPlan", x: jax.Array):
    """The ``(dim, left, right)`` ghost faces of one fused block's
    window, in the order they nest around the shard ``x``: per dim, the
    ``sweeps*halo``-deep neighbour faces of a sharded dim (exchanged per
    the plan's strategy) or the boundary extension of an unsharded one.

    Only faces travel.  A later dim's faces span the earlier dims'
    ghosts, so they carry the corners: each is built from the edge rows
    of the shard and of the faces already made (:func:`_edge_rows`),
    never from a widened copy of the shard."""
    mode, value = plan.boundary_mode, plan.boundary_value
    faces = []
    for d, halo in enumerate(plan.deep_halo):
        if halo == 0:
            continue
        size = x.shape[d]
        edge = _nest(_edge_rows(x, d, halo, size),
                     [(e, _edge_rows(lf, d, halo, size),
                       _edge_rows(rf, d, halo, size))
                      for e, lf, rf in faces])
        name = _axis_name(plan, d)
        if name is not None:
            ext = exchange_halo_1axis(edge, d, halo, name, mode=mode,
                                      value=value,
                                      strategy=plan.exchange[d], size=size)
        else:
            pad = [0] * x.ndim
            pad[d] = halo
            ext = _ref.pad_boundary(edge, pad, mode, value)
        rows = ext.shape[d]
        faces.append((d, lax.slice_in_dim(ext, 0, halo, axis=d),
                      lax.slice_in_dim(ext, rows - halo, rows, axis=d)))
    return faces


def _window(plan: "_plan.ExecutionPlan", x: jax.Array, faces,
            extent: Sequence[int]) -> jax.Array:
    """The shard with its ghost ``faces`` around it, out to ``extent``
    (the far end past the faces is never read): one pad of the shard,
    the pass that reads and writes the whole window, then each face
    written into it in place.  An unsharded dim's constant ghosts are
    the pad's own fill and are not written again."""
    fill = _fill(plan)
    lo = [0] * x.ndim
    for d, left, _ in faces:
        lo[d] = left.shape[d]
    window = jnp.pad(x, [(w, e - n - w) for w, e, n
                         in zip(lo, extent, x.shape)],
                     constant_values=jnp.asarray(fill or 0, x.dtype))
    for d, left, right in faces:
        if _axis_name(plan, d) is None and fill is not None:
            continue
        at = [lo[e] if e > d else 0 for e in range(x.ndim)]
        window = lax.dynamic_update_slice(window, left, at)
        at[d] = lo[d] + x.shape[d]
        window = lax.dynamic_update_slice(window, right, at)
    return window


def _local_multisweep(plan: "_plan.ExecutionPlan", x: jax.Array) -> jax.Array:
    """Shard-local fused compute: exchange the ``sweeps*halo``-deep
    faces once (on sharded dims per the plan's per-axis strategy,
    boundary-extended elsewhere), assemble the kernel's window from the
    shard and the faces in one pass, then apply all ``sweeps`` stencil
    applications to it.  Every decision — exchange strategy, tile, halo
    depth — was resolved at lowering time."""
    spec = plan.spec
    deep = plan.deep_halo
    faces = _faces(plan, x)
    origin, grid_shape = [], []
    for d in range(spec.ndim):
        name = _axis_name(plan, d)
        if name is not None:
            origin.append(lax.axis_index(name) * x.shape[d])
            grid_shape.append(x.shape[d] * lax.psum(1, name))
        else:
            origin.append(0)
            grid_shape.append(x.shape[d])
    # The faces fetched the sweeps * halo deep halo (a pipeline's halo
    # is its per-dim sum of stage radii), so every stage of every sweep
    # computes from exchanged data: one collective launch pair per
    # sharded axis per fused block.
    if plan.backend in _plan.KERNEL_BACKENDS:
        from repro.kernels import engine as keng  # lazy: optional dep
        extent = keng.window_extent(x.shape, plan.tile, deep,
                                    x.dtype.itemsize)
        return keng.stencil_window_sweep(
            spec, _window(plan, x, faces, extent), x.shape, origin,
            grid_shape, tile=plan.tile, sweeps=plan.sweeps,
            interpret=plan.interpret,
            mesh_tag=exchange_tag(plan, x.shape, x.dtype.itemsize, extent))
    window = _window(plan, x, faces,
                     [n + 2 * w for n, w in zip(x.shape, deep)])
    return _ref.masked_window_pipeline(
        window, plan.stages, x.shape, plan.sweeps, origin, grid_shape,
        x.dtype).astype(x.dtype)


def execute_plan(plan: "_plan.ExecutionPlan", x: jax.Array) -> jax.Array:
    """One fused distributed step of a lowered plan: deep halo exchange +
    ``plan.sweeps`` shard-local applications, as a ``shard_map`` over the
    plan's mesh applied to the global array."""
    if not plan.is_distributed:
        raise ValueError("plan has no mesh; use the single-device executors")
    pspec = P(*plan.grid_axes)
    local = functools.partial(_local_multisweep, plan)
    # pallas_call has no shard_map replication rule; the local fn is
    # purely per-shard, so disabling the check is sound there.
    step = jax.shard_map(local, mesh=plan.mesh, in_specs=(pspec,),
                         out_specs=pspec,
                         check_vma=(plan.backend not in _plan.KERNEL_BACKENDS))
    return step(x)


def distributed_stencil_fn(
    spec: "StencilSpec | _stencil.StencilPipeline",
    mesh: Mesh,
    grid_axes: Sequence[str | None],
    iters: int = 1,
    *,
    sweeps: int = 1,
    backend: Literal["ref", "pallas"] = "ref",
    tile: Sequence[int] | Literal["auto"] | None = None,
    interpret: bool | None = None,
) -> Callable[[jax.Array], jax.Array]:
    """Build a jit-able global-array stencil function on ``mesh``.

    ``grid_axes[d]`` names the mesh axis sharding grid dim ``d`` (None =
    replicated/unsharded).  Returns a function mapping the global grid to
    the global grid after ``iters`` Jacobi sweeps.  ``spec`` may be a
    :class:`~repro.core.stencil.StencilPipeline`: a fusable chain
    exchanges one ``sweeps * sum(stage radii)``-deep halo per fused step
    and runs every stage application shard-locally; a non-fusable chain
    (mixed periodic/non-periodic stages) falls back to per-stage
    distributed plans inside ``plan.execute``.

    ``sweeps=t`` applies temporal blocking across the wire: each fused
    step exchanges one ``t*halo``-deep halo (multi-hop when a shard is
    narrower than the deep halo) and runs ``t`` applications locally, so
    collective launches drop ~t× at roughly equal wire volume.  ``iters``
    decomposes as ``q*t + r`` via ``plan.decompose`` exactly like
    ``CasperEngine.run`` — ``q`` fused steps plus one narrower remainder
    step whose plan comes from the plan cache.  ``backend`` selects the
    shard-local compute: the ``ref`` einsum path or the Pallas kernel
    (``tile``/``tile="auto"`` autotunes on the *shard* shape inside
    ``plan.lower``; ``interpret=None`` auto-detects: interpret mode on
    CPU, compiled on TPU).  Both backends dispatch per-application
    compute on the factorization recorded on the plan through the shared
    masked multi-sweep core, so structure-specialized specs stay f64
    bit-identical across the distributed path too.
    """
    if len(grid_axes) != spec.ndim:
        raise ValueError("grid_axes must have one entry per grid dim")
    if sweeps < 1:
        raise ValueError(f"sweeps must be >= 1, got {sweeps}")
    if iters < 0:
        raise ValueError(f"iters must be >= 0, got {iters}")
    if backend not in ("ref",) + _plan.KERNEL_BACKENDS:
        raise ValueError(f"unknown backend {backend!r}")
    pspec = P(*grid_axes)
    axes = tuple(grid_axes)

    def run(x):
        plan = _plan.lower(spec, x.shape, x.dtype, backend=backend,
                           sweeps=sweeps, tile=tile, mesh=mesh,
                           grid_axes=axes, interpret=interpret)
        return _plan.run_plan(plan, x, iters)

    in_sh = NamedSharding(mesh, pspec)
    return jax.jit(run, in_shardings=(in_sh,), out_shardings=in_sh)


def sharding_for(mesh: Mesh, grid_axes: Sequence[str | None]) -> NamedSharding:
    return NamedSharding(mesh, P(*grid_axes))

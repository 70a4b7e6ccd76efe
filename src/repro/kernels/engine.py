"""Unified N-D temporal-blocking stencil engine (Pallas, TPU recipe).

One generic `pallas_call` emitter replaces the three near-duplicate
per-rank kernels the seed shipped (`stencil1d/2d/3d.py`).  For any
:class:`~repro.core.stencil.StencilSpec` or fusable
:class:`~repro.core.stencil.StencilPipeline` of rank 1-3 — a spec is
the one-stage chain of itself, so each mechanism has one entry point
for both (:func:`stencil_window_sweep`, :func:`stencil_sweep`,
:func:`stencil_apply`, :func:`execute_plan`) — it builds a kernel
parameterized by

* **tile** — the VMEM output block owned by one grid step (the paper's
  "stencil segment block", §4.1);
* **halo** — taken from the kernel (a chain's is the per-dim sum of its
  stage radii); the grid stays in HBM and each grid
  step DMAs its window into VMEM, rounded out to the HBM layout's
  (8, 128) granule and cut to the exact window in VMEM — the software
  analogue of Casper's unaligned-load hardware;
* **dtype** — accumulation runs in f32 for sub-f32 inputs and in the
  input dtype otherwise, so f64 results are bit-identical to the
  `core.ref` oracle;
* **sweeps** — *temporal blocking*: ``sweeps=t`` fuses ``t`` Jacobi
  applications inside a single kernel invocation.  The fetched halo is
  widened to ``t*halo`` per side and the ``t`` applications iterate on
  the VMEM-resident window, each shrinking it by one halo layer.  HBM
  traffic per point drops from ``t*(read + write)`` to roughly
  ``read + write`` — the ~t× reduction the paper's arithmetic-intensity
  analysis (§2, Fig. 1) identifies as the only lever for bandwidth-bound
  stencils.  This is the cache-aware time tiling of Frumkin & Van der
  Wijngaart applied at VMEM granularity.

Boundary semantics (each stage's ``boundary``: zero / constant(c) /
periodic / reflect) are preserved across fused sweeps: the fetched
window is built with the mode's ghost extension (``ref.pad_boundary``),
and between inner applications ghost elements — identified by *global*
coordinate — are restored to the boundary extension of the
intermediate: masked to the fill value (zero/constant), re-mirrored
from the interior by an in-window gather (reflect), or left alone
(periodic: the stencil of a periodically extended window keeps its
ghosts bitwise equal to their wrapped interior counterparts).  Each is
the closed form of the oracle re-padding before every sweep, so fused
results stay f64 bit-identical to chained oracle applications under
all four modes — see docs/boundaries.md.

**Pad-free fused sweeps**: :func:`stencil_sweep` does not materialize a
boundary-padded copy of the whole grid per fused call.  Each tile's
window is DMA'd straight from the unpadded grid, its ghost slabs taken
from the far side of the grid (:func:`_fetch_pieces`) — exactly the
periodic extension — and the kernel then overwrites the ghosts of the
other modes from their closed form: fill masking (zero/constant) or the
static-shift mirror (reflect).  The ghost values are bitwise identical
to what ``ref.pad_boundary`` would have produced, so f64 parity with the
oracle is untouched while the per-call ``O(grid)`` pad read+write
round-trip disappears (:func:`hbm_traffic` charges it to the unfused
baseline only).  Grids that are not a multiple of the tile, or tiles
shallower than the aligned fetch depth, fall back to the padded path.

**Structure specialization**: per-application compute inside the kernel
dispatches on each stage's ``structure`` (star / separable / dense —
see ``repro.core.stencil.factor_taps``) through the one fused core,
``ref.masked_window_pipeline``, so separable specs (``blur2d``,
``star33_3d``'s core) run factored axis passes with ``O(sum)`` instead
of ``O(prod)`` tap temporaries, bit-identically to the oracle in f64.
A chain's intermediate stage fields stay in VMEM; between stages the
core restores ghosts per the *next* stage's mode.

A leading batch dimension (``vmap``, see :func:`stencil_apply`) becomes
a leading grid axis of the same kernel, so a stack of independent grids
shares one compiled kernel.  ``interpret=None`` (the default everywhere) resolves
to interpret mode exactly when the backend is CPU, so TPU users get
compiled kernels without passing a flag.
"""
from __future__ import annotations

import functools
import itertools
import math
from typing import Sequence

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import plan as _plan
from repro.core import ref as _ref
from repro.core import perfmodel as _pm
from repro.core import trace as _trace
from repro.core.plan import resolve_interpret  # canonical auto-detect
from repro.core.stencil import (StencilPipeline, StencilSpec, as_stages,
                                fusable)

# Tile defaulting/validation is a lowering decision and lives in
# repro.core.plan; re-exported here for the existing call sites.
DEFAULT_TILES = _plan.DEFAULT_TILES
default_tile = _plan.default_tile
_normalize_tile = _plan.normalize_tile


def _acc_dtype(dtype) -> jnp.dtype:
    """f32 accumulation for narrow inputs; native otherwise (f64 exact)."""
    if jnp.dtype(dtype).itemsize < 4:
        return jnp.dtype(jnp.float32)
    return jnp.dtype(dtype)


def _fetch_pieces(i, tile: int, n: int, lo: int, ext: int, wrap: bool):
    """The DMA pieces ``(dst offset, size, src start)`` that fill one
    dim of tile ``i``'s ``ext``-long buffer.

    ``wrap`` (pad-free): the source is the unpadded ``n``-point grid and
    the buffer spans ``[i*tile - lo, (i+1)*tile + lo)``; the two ghost
    slabs come from ``(start mod n)``, so a tile at the edge fetches the
    far side of the grid — exactly the periodic extension, and data the
    ghost restoration overwrites for the other modes.  Otherwise the
    source is a window that already carries its ghost layers, and one
    piece from ``i*tile`` covers the buffer."""
    if not wrap:
        return ((0, ext, i * tile),)
    if not lo:
        return ((0, tile, i * tile),)
    return ((0, lo, (i * tile - lo + n) % n),
            (lo, tile, i * tile),
            (lo + tile, lo, (i * tile + tile) % n))


def _kernel_tag(strategy: str, sweeps: int, tile: Sequence[int],
                grid: Sequence[int], ext: Sequence[int],
                itemsize: int, mesh_tag=None) -> dict[str, str]:
    """The metadata of one fused ``pallas_call``, all strings; bytes and
    steps count one execution of the kernel op:

    ``casper``       ``"fused"``;
    ``strategy``     ``"pad-free"`` (ghost slabs fetched by wrapped DMAs
                     from the unpadded grid) or ``"window"`` (the
                     source already carries them: the padded-window
                     fallback and the mesh path's exchanged block);
    ``sweeps``       stencil applications fused in the call;
    ``tile``         the output tile, e.g. ``"32x512"``;
    ``grid_steps``   grid steps, a batch axis included;
    ``fetch_bytes``  bytes all steps DMA from HBM into VMEM: each step
                     fills one whole aligned ``ext`` buffer;
    ``write_bytes``  bytes of all the output blocks.

    ``mesh_tag`` adds the mesh path's fields
    (:func:`repro.core.halo.exchange_tag`): ``shards``, the mesh's
    extent per sharded grid dim (``"2x2"``), and ``exchange_bytes``,
    the bytes a shard received in the halo exchange of this block.
    """
    steps = math.prod(grid)
    return {"casper": "fused", "strategy": strategy, "sweeps": str(sweeps),
            "tile": "x".join(str(t) for t in tile),
            "grid_steps": str(steps),
            "fetch_bytes": str(steps * math.prod(ext) * itemsize),
            "write_bytes": str(steps * math.prod(tile) * itemsize),
            **(mesh_tag or {})}


def window_extent(out_shape: Sequence[int], tile: Sequence[int],
                  wide: Sequence[int], itemsize: int) -> tuple[int, ...]:
    """Extents of the source a ``window`` kernel fetches from: the
    ``out_shape`` block with ``wide`` ghost layers per side, its far end
    rounded out so that the last tile's aligned buffer (``tile +
    2*wide`` rounded up to the HBM granule,
    :func:`repro.core.perfmodel.fetch_grain`) lies inside it."""
    grain = _pm.fetch_grain(len(tile), itemsize)
    return tuple(-(-n // t) * t - t + -(-(t + 2 * w) // g) * g
                 for n, t, w, g in zip(out_shape, tile, wide, grain))


def _fused_kernel(org_ref, src_ref, o_ref, buf, sem, *, core, tile, wide,
                  lo, cut, grain, grid_shape, wrap, fix, batched):
    """One grid step of every fused kernel: DMA the tile's aligned
    window from HBM into VMEM, cut the ``sweeps*halo`` window out of it,
    restore its boundary ghosts (pad-free fetches only; a padded source
    already carries them) and run ``core`` — the shared multi-sweep
    (or fused-chain) body — on it.

    ``org_ref`` (SMEM) holds the global coordinate of the call's
    interior origin: zeros on one device, the shard offset in the mesh
    path, so ghost restoration always works on *global* coordinates.
    A ``batched`` call walks a leading grid axis over the source's
    leading batch dim.
    """
    ndim = len(tile)
    lead = (pl.program_id(0),) if batched else ()
    ids = tuple(pl.program_id(d + len(lead)) for d in range(ndim))
    per_dim = [_fetch_pieces(ids[d], tile[d], grid_shape[d], lo[d],
                             buf.shape[d], wrap) for d in range(ndim)]
    copies = []
    for k, combo in enumerate(itertools.product(*per_dim)):
        src = lead + tuple(
            pl.ds(st if g == 1 else pl.multiple_of(st, g), size)
            for (_, size, st), g in zip(combo, grain))
        dst = tuple(pl.ds(off, size) for off, size, _ in combo)
        copies.append(pltpu.make_async_copy(src_ref.at[src], buf.at[dst],
                                            sem.at[k]))
    for cp in copies:
        cp.start()
    for cp in copies:
        cp.wait()
    x = jax.lax.slice(buf[...], cut,
                      tuple(c + t + 2 * w for c, t, w in zip(cut, tile, wide)))
    starts = tuple(org_ref[d] + ids[d] * tile[d] for d in range(ndim))
    if fix is not None:
        mode, value = fix
        x = _ref._restore_ghosts(x, mode, value,
                                 tuple(s - w for s, w in zip(starts, wide)),
                                 grid_shape, wide)
    o_ref[...] = core(x, starts).astype(o_ref.dtype)


def _fused_call(core, src: jax.Array, out_shape: Sequence[int], origin,
                grid_shape: Sequence[int], tile: Sequence[int],
                wide: Sequence[int], *, fix, sweeps: int,
                interpret: bool, mesh_tag=None) -> jax.Array:
    """The one ``pallas_call`` emitter behind every fused kernel.

    The source stays in HBM (``memory_space=pl.ANY``) and each grid
    step DMAs its window into a VMEM buffer rounded out to the HBM
    layout's granule (:func:`repro.core.perfmodel.fetch_grain`): Mosaic
    only copies whole (8, 128) tiles, so the window is fetched aligned
    and the exact window is cut out of it in VMEM.

    ``fix=(mode, value)`` selects the **pad-free** fetch: ``src`` is the
    unpadded grid (``out_shape == grid_shape``, every extent a multiple
    of its tile, every tile at least the fetch depth), the buffer
    carries the ``sweeps*halo`` ghost depth rounded up to the granule on
    both sides, and the kernel restores the boundary ghosts itself.
    ``fix=None`` takes ``src`` as a window that already carries ``wide``
    ghost layers per side (the padded-window fallback and the mesh
    path's exchanged block): tile ``i``'s window starts at ``i*tile``,
    and only the buffer's far end is rounded up.

    The call is named :data:`repro.core.trace.KERNEL_NAME` and tagged
    with what one execution of it does (:func:`_kernel_tag`), so a
    profile of the chip says which strategy, tile and sweep depth each
    kernel event ran, and how many grid steps and HBM bytes it took;
    ``mesh_tag`` adds what the mesh path exchanged for it.
    """
    ndim = len(tile)
    tile = tuple(tile)
    wide = tuple(wide)
    out_shape = tuple(out_shape)
    grid_shape = tuple(int(n) for n in grid_shape)
    grain = _pm.fetch_grain(ndim, src.dtype.itemsize)
    lo = _pm.fetch_halo(wide, grain)
    grid_dims = tuple(-(-n // t) for n, t in zip(out_shape, tile))
    padded = tuple(g * t for g, t in zip(grid_dims, tile))
    wrap = fix is not None
    if wrap:
        if (out_shape != grid_shape or padded != out_shape
                or any(t < f for t, f in zip(tile, lo))):
            raise ValueError(
                f"pad-free fetch needs grid {out_shape} to be a multiple "
                f"of tile {tile} with every tile >= its fetch depth {lo}")
        ext = tuple(t + 2 * f for t, f in zip(tile, lo))
        cut = tuple(f - w for f, w in zip(lo, wide))
    else:
        ext = tuple(-(-(t + 2 * w) // g) * g
                    for t, w, g in zip(tile, wide, grain))
        cut = (0,) * ndim
        extent = window_extent(out_shape, tile, wide, src.dtype.itemsize)
        if src.shape != extent:
            src = jnp.pad(src, [(0, e - n)
                                for e, n in zip(extent, src.shape)])
    if not interpret and any(t % g for t, g in zip(tile, grain)):
        raise ValueError(f"compiled tile {tile} is not a multiple of the "
                         f"HBM granule {grain}")
    n_copies = math.prod(len(_fetch_pieces(0, t, 1, f, e, wrap))
                         for t, f, e in zip(tile, lo, ext))
    scratch = [pltpu.VMEM(ext, src.dtype),
               pltpu.SemaphoreType.DMA((n_copies,))]

    def emit(org, src, batch):
        tag = _kernel_tag("pad-free" if wrap else "window", sweeps, tile,
                          batch + grid_dims, ext, src.dtype.itemsize,
                          mesh_tag)
        kernel = functools.partial(
            _fused_kernel, core=core, tile=tile, wide=wide, lo=lo, cut=cut,
            grain=grain, grid_shape=grid_shape, wrap=wrap, fix=fix,
            batched=bool(batch))
        if batch:
            out_spec = pl.BlockSpec((pl.Squeezed(),) + tile,
                                    lambda b, *ids: (b,) + ids)
        else:
            out_spec = pl.BlockSpec(tile, lambda *ids: ids)
        return pl.pallas_call(
            kernel,
            grid=batch + grid_dims,
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      pl.BlockSpec(memory_space=pl.ANY)],
            out_specs=out_spec,
            out_shape=jax.ShapeDtypeStruct(batch + padded, src.dtype),
            scratch_shapes=scratch,
            interpret=interpret,
            name=_trace.KERNEL_NAME,
            metadata=tag,
        )(org, src)

    # A memory_space=ANY operand has no pallas batching rule, so a
    # vmapped call (a serving bucket) walks the batch as a leading grid
    # axis of the same kernel instead.  Rank 1 maps the kernel over the
    # rows: a row of a 2-D array is laid out in (8, 128) tiles, which
    # Mosaic cannot DMA into a 1-D buffer's 1024-word tiles.
    @jax.custom_batching.custom_vmap
    def call(org, src):
        return emit(org, src, ())

    @call.def_vmap
    def _call_batched(axis_size, in_batched, org, src):
        if in_batched[0]:
            raise NotImplementedError("a batched origin is not supported")
        if not in_batched[1]:
            src = jnp.broadcast_to(src, (axis_size,) + src.shape)
        if ndim == 1:
            return jax.lax.map(lambda row: emit(org, row, ()), src), True
        return emit(org, src, (axis_size,)), True

    out = call(jnp.asarray(origin, jnp.int32).reshape(ndim), src)
    if padded == out_shape:
        return out
    return out[tuple(slice(0, n) for n in out_shape)]


def _core(spec, tile, sweeps, grid_shape, acc_dtype):
    """The kernel body's compute: the one fused core over the stage
    chain of ``spec`` (a plain spec is its own one-stage chain)."""
    stages = as_stages(spec)

    def core(x, starts):
        return _ref.masked_window_pipeline(
            x, stages, tile, sweeps, starts, grid_shape, acc_dtype)
    return core


def stencil_window_sweep(spec: StencilSpec | StencilPipeline,
                         window: jax.Array,
                         out_shape: Sequence[int],
                         origin,
                         grid_shape: Sequence[int],
                         tile: Sequence[int] | int | None = None,
                         sweeps: int = 1,
                         interpret: bool | None = None,
                         mesh_tag=None) -> jax.Array:
    """``sweeps`` fused applications of ``spec`` (a spec, or a fusable
    pipeline chain) to a block that already carries its
    ``sweeps*halo``-wide halo (a pipeline's ``halo`` is the per-dim sum
    of its stage radii).

    ``window`` has shape ``out_shape + 2*sweeps*halo`` per dim, or
    already the :func:`window_extent` the kernel fetches from (its far
    end rounded out; the mesh path assembles it so), and must
    carry stage 0's boundary ghost extension in its halo layers
    (``ref.pad_boundary`` on a single device, the halo exchange in the
    distributed path); the interior's origin sits at global coordinate
    ``origin`` (static ints or a traced value, e.g. ``axis_index`` inside
    shard_map) of a ``grid_shape`` grid, against which the between-sweep
    ghost restoration is evaluated.  This is the shard-local entry point
    of the distributed deep-halo path and of the slab executor;
    :func:`stencil_sweep` uses it for the single-device padded-window
    fallback.  ``mesh_tag`` (the mesh path's) adds its fields to the
    kernel tag (:func:`_kernel_tag`).  A chain that mixes periodic with
    non-periodic stages cannot run fused and is refused.
    """
    if sweeps < 1:
        raise ValueError(f"sweeps must be >= 1, got {sweeps}")
    if not fusable(spec):
        raise ValueError(
            f"{spec.name}: mixed periodic/non-periodic stages cannot "
            "run fused; lower the pipeline and use the staged plan")
    interpret = resolve_interpret(interpret)
    tile = _normalize_tile(spec, tile)
    out_shape = tuple(out_shape)
    wide = tuple(sweeps * h for h in spec.halo)
    want = tuple(n + 2 * w for n, w in zip(out_shape, wide))
    if window.shape not in (want, window_extent(out_shape, tile, wide,
                                                window.dtype.itemsize)):
        raise ValueError(
            f"window shape {window.shape} != out_shape + 2*sweeps*halo "
            f"{want} (or its window_extent)")
    grid_shape = tuple(int(n) for n in grid_shape)
    core = _core(spec, tile, sweeps, grid_shape, _acc_dtype(window.dtype))
    return _fused_call(core, window, out_shape, origin, grid_shape, tile,
                       wide, fix=None, sweeps=sweeps, interpret=interpret,
                       mesh_tag=mesh_tag)


def _sweep(spec, grid: jax.Array, tile: tuple[int, ...], sweeps: int,
           interpret: bool, strategy: str) -> jax.Array:
    """One fused block on one grid under a resolved ghost ``strategy``:
    the pad-free fetch, or the padded-window fallback
    (:func:`stencil_window_sweep` on a ``ref.pad_boundary`` window)."""
    wide = tuple(sweeps * h for h in spec.halo)
    origin = (0,) * spec.ndim
    if strategy == "padded-window":
        window = _ref.pad_boundary(grid, wide, spec.boundary_mode,
                                   spec.boundary_value)
        return stencil_window_sweep(
            spec, window, grid.shape, origin, grid.shape,
            tile=tile, sweeps=sweeps, interpret=interpret)
    core = _core(spec, tile, sweeps, grid.shape, _acc_dtype(grid.dtype))
    return _fused_call(core, grid, grid.shape, origin, grid.shape,
                       tile, wide,
                       fix=(spec.boundary_mode, spec.boundary_value),
                       sweeps=sweeps, interpret=interpret)


def _batched(spec, grid: jax.Array, fn):
    """``fn`` on one grid, or ``jax.vmap`` of it over a leading batch
    dim (one shared kernel for the stack)."""
    if grid.ndim == spec.ndim:
        return fn(grid)
    if grid.ndim == spec.ndim + 1:
        return jax.vmap(fn)(grid)
    raise ValueError(
        f"grid rank {grid.ndim} incompatible with spec ndim {spec.ndim} "
        f"(expected ndim or ndim+1 for a batched grid)")


def stencil_sweep(spec: StencilSpec | StencilPipeline, grid: jax.Array,
                  tile: Sequence[int] | int | None = None,
                  sweeps: int = 1,
                  interpret: bool | None = None) -> jax.Array:
    """``sweeps`` fused applications of ``spec`` (a spec, or a
    pipeline chain) to ``grid`` under its boundary modes, **pad-free**:
    the kernel fetches its window straight from the unpadded grid and
    materializes boundary ghosts in-kernel (see :func:`_fused_kernel`),
    so no padded copy of the grid is built per fused call, and a
    chain's intermediate stage fields stay in VMEM.

    Equivalent to ``sweeps`` chained :func:`repro.core.ref.apply_stencil`
    (:func:`~repro.core.ref.apply_pipeline`) calls, but with a single
    HBM read/write per point instead of one per stage application.
    ``grid`` rank must equal ``spec.ndim`` (1-3); use
    :func:`stencil_apply` for a leading batch dimension.  Grids that are
    not a multiple of the tile, or whose tile is shallower than the
    aligned fetch depth, fall back to the padded path
    (:func:`stencil_window_sweep` on a ``ref.pad_boundary`` window —
    identical results, the ghosts are bitwise equal either way); the
    choice is ``plan.ghost_strategy_for``'s.  A chain that mixes
    periodic with non-periodic stages runs stage by stage through its
    staged plan (``plan.execute``).
    """
    if grid.ndim != spec.ndim:
        raise ValueError(f"grid rank {grid.ndim} != spec ndim {spec.ndim}")
    if sweeps < 1:
        raise ValueError(f"sweeps must be >= 1, got {sweeps}")
    interpret = resolve_interpret(interpret)
    if not fusable(spec):
        return _plan.execute(
            _plan.lower(spec, grid.shape, grid.dtype, backend="pallas",
                        sweeps=sweeps, tile=tile, interpret=interpret), grid)
    tile = _normalize_tile(spec, tile)
    strategy = _plan.ghost_strategy_for(spec, grid.shape,
                                        grid.dtype.itemsize, sweeps, tile)
    return _sweep(spec, grid, tile, sweeps, interpret, strategy)


def stencil_apply(spec: StencilSpec | StencilPipeline, grid: jax.Array,
                  tile: Sequence[int] | int | None = None,
                  sweeps: int = 1,
                  interpret: bool | None = None) -> jax.Array:
    """Rank-dispatching entry point with an optional leading batch dim.

    ``grid.ndim == spec.ndim``    → one grid (:func:`stencil_sweep`);
    ``grid.ndim == spec.ndim+1``  → dim 0 is a batch of independent
    grids, mapped with ``jax.vmap`` over one shared kernel.
    """
    interpret = resolve_interpret(interpret)
    return _batched(spec, grid, functools.partial(
        stencil_sweep, spec, tile=tile, sweeps=sweeps,
        interpret=interpret))


def execute_plan(plan, grid: jax.Array) -> jax.Array:
    """Thin Pallas executor of one lowered
    :class:`~repro.core.plan.ExecutionPlan` (a spec's or a fused
    chain's): one fused block of ``plan.sweeps`` applications with the
    plan's resolved tile and ghost strategy (an optional leading batch
    dim vmaps over one shared kernel, exactly as
    :func:`stencil_apply`)."""
    if plan.backend != "pallas":
        raise ValueError(f"not a pallas plan: backend={plan.backend!r}")
    return _batched(plan.spec, grid, functools.partial(
        _sweep, plan.spec, tile=plan.tile, sweeps=plan.sweeps,
        interpret=plan.interpret, strategy=plan.ghost_strategy))


def run_sweeps(spec: StencilSpec, grid: jax.Array, iters: int,
               tile: Sequence[int] | int | None = None,
               sweeps: int = 1,
               interpret: bool | None = None) -> jax.Array:
    """``iters`` total applications, fused ``sweeps`` at a time.

    Lowers one plan (through the process-wide plan cache) and runs
    ``plan.decompose(iters) = (q, r)``: ``q`` fused calls rolled into a
    single ``lax.scan`` (one traced/compiled step instead of ``q``
    unrolled copies of the kernel graph) plus one remainder call whose
    narrower plan also comes from the cache, so any ``iters`` is exact
    for any blocking factor.  A pad-free plan's scan runs two calls a
    step: its kernel reads the carried grid in place, and with one call
    a step XLA would copy the whole grid after every call
    (``plan.run_plan``).
    """
    plan = _plan.lower(spec, _plan._grid_shape_for(spec, grid), grid.dtype,
                       backend="pallas", sweeps=sweeps, tile=tile,
                       interpret=interpret)
    return _plan.run_plan(plan, grid, iters)


# ---------------------------------------------------------------------------
# Analytic HBM-traffic model for temporal blocking
# ---------------------------------------------------------------------------
def hbm_traffic(spec: StencilSpec, shape: Sequence[int],
                tile: Sequence[int] | None = None,
                sweeps: int = 1, itemsize: int = 4) -> dict[str, float]:
    """Bytes moved between HBM and VMEM for ``sweeps`` applications.

    ``fused``    — one **pad-free** kernel invocation with a
                   ``sweeps*halo`` window: each tile reads
                   ``prod(tile + 2*sweeps*halo)`` once and writes
                   ``prod(tile)`` once, straight against the unpadded
                   grid (ghosts are materialized in-kernel, no pad
                   traffic).
    ``unfused``  — ``sweeps`` single-sweep invocations of the legacy
                   padded pipeline: single-halo windows *plus*, per
                   invocation, the host-side ``pad_boundary`` round-trip
                   the pipeline used to pay — read the ``prod(shape)``
                   grid once and write the ``prod(shape + 2*halo)``
                   padded copy once.  (The seed's model omitted this
                   term on both sides, under-reporting the baseline and
                   misguiding sweep selection.)
    ``reduction``      = unfused / fused — now > sweeps, since fusing
                         also deletes the per-sweep pad copy (§2's
                         ~sweeps× window saving stacks with it).
    ``legacy_fused_bytes`` — what the *padded* fused pipeline moved
                   (``fused`` + one ``sweeps*halo``-deep pad copy):
                   strictly greater than ``fused_bytes`` for every spec,
                   the modeled win of the pad-free path alone.
    """
    if tile is None:
        tile = DEFAULT_TILES[spec.ndim]
    tile = tuple(tile)
    halo = spec.halo
    n_tiles = math.prod(-(-n // t) for n, t in zip(shape, tile))
    out_b = math.prod(tile) * itemsize
    grid_b = math.prod(shape) * itemsize

    def window_bytes(layers: int) -> int:
        return math.prod(t + 2 * layers * h
                         for t, h in zip(tile, halo)) * itemsize

    def pad_copy_bytes(layers: int) -> int:
        padded = math.prod(n + 2 * layers * h
                           for n, h in zip(shape, halo)) * itemsize
        return grid_b + padded          # read grid once, write padded copy

    fused = n_tiles * (window_bytes(sweeps) + out_b)
    unfused = sweeps * (n_tiles * (window_bytes(1) + out_b)
                        + pad_copy_bytes(1))
    return {
        "fused_bytes": float(fused),
        "unfused_bytes": float(unfused),
        "reduction": unfused / fused,
        "halo_overhead": n_tiles * window_bytes(sweeps) / fused,
        "pad_bytes_unfused": float(sweeps * pad_copy_bytes(1)),
        "legacy_fused_bytes": float(fused + pad_copy_bytes(sweeps)),
    }


def hbm_pipeline_traffic(pipeline: StencilPipeline, shape: Sequence[int],
                         tile: Sequence[int] | None = None,
                         sweeps: int = 1,
                         itemsize: int = 4) -> dict[str, float]:
    """Modeled HBM bytes of ``sweeps`` fused chain applications vs the
    stage-by-stage baseline.

    ``fused``  — one fused-chain kernel invocation: each tile reads the
                 ``sweeps * H``-widened window once (``H`` = per-dim sum
                 of stage radii) and writes one tile — every
                 intermediate stage field lives in VMEM.
    ``staged`` — the per-stage chain at its *best*: each stage as one
                 pad-free single-sweep kernel per application, so each
                 of the ``sweeps * n_stages`` stage passes reads its own
                 (stage-radius) windows and writes its intermediate
                 field to HBM.  This deliberately under-charges the
                 baseline (no pad round-trips), so the reported
                 ``reduction`` is a lower bound on the fusion win.
    ``intermediate_bytes`` — the HBM round-trips the fusion deletes: the
                 ``sweeps * n_stages - 1`` intermediate field writes +
                 reads the staged chain pays between stage passes.
    """
    if tile is None:
        tile = DEFAULT_TILES[pipeline.ndim]
    tile = tuple(tile)
    n_tiles = math.prod(-(-n // t) for n, t in zip(shape, tile))
    out_b = math.prod(tile) * itemsize

    def window_bytes(layers: Sequence[int]) -> int:
        return math.prod(t + 2 * w for t, w in zip(tile, layers)) * itemsize

    fused = n_tiles * (window_bytes(tuple(sweeps * h
                                          for h in pipeline.halo)) + out_b)
    staged = sweeps * sum(
        n_tiles * (window_bytes(stage.halo) + out_b)
        for stage in pipeline.stages)
    grid_b = math.prod(shape) * itemsize
    passes = sweeps * pipeline.n_stages
    return {
        "fused_bytes": float(fused),
        "staged_bytes": float(staged),
        "reduction": staged / fused,
        "intermediate_bytes": float(2 * (passes - 1) * grid_b),
        "n_stage_passes": float(passes),
    }

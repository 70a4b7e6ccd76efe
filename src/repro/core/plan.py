"""ExecutionPlan: the one spec→plan lowering pipeline feeding every backend.

Casper's central separation — the host assembles *what* a stencil
computes once, the memory system decides *how* to execute it at peak
bandwidth — used to be smeared across five modules: each execution layer
(jnp/numpy oracles, Pallas engine, distributed halo path, SPU VM)
re-derived the structure factorization, the boundary-ghost strategy, the
tile choice and the ``iters = q*sweeps + r`` decomposition for itself.
This module is now the single home of those decisions:

``lower(spec, shape, dtype, *, backend, sweeps, tile, mesh, grid_axes,
interpret)`` resolves, once, everything a backend needs, for a
:class:`~repro.core.stencil.StencilSpec` or a
:class:`~repro.core.stencil.StencilPipeline` alike: a spec is the
one-stage chain :func:`~repro.core.stencil.as_stages` of itself, so one
lowering ladder, one autotuner and one cost model serve both —

* whether the chain **fuses** (:func:`repro.core.stencil.fusable`; a
  mixed periodic/non-periodic chain runs ``"staged"``);
* the tap **factorization** of a spec
  (:func:`repro.core.stencil.factor_taps`; each stage of a pipeline
  keeps its own);
* the **boundary-ghost strategy**: per-sweep ``pad_boundary`` for the
  oracles (``"pad"``), in-kernel ghost materialization vs the padded
  window fallback for Pallas (:func:`ghost_strategy_for`), the
  wrap-ring / zero-fill / local edge-fixup exchange per sharded axis for
  the distributed path (:func:`exchange_strategy_for`), and the VM's
  per-access ghost service (``"stream"``);
* the **tile** (``"auto"`` runs the :mod:`repro.kernels.tune` autotuner
  here and nowhere else; for distributed plans it tunes on the *shard*
  shape);
* the **iteration decomposition** (``plan.decompose(iters)``) and the
  **remainder plan** (``plan.remainder(r)`` — lowered through the same
  cache, so remainders never re-autotune at trace time);
* the halo depth (``plan.deep_halo = sweeps * halo``) and the assembled
  SPU :class:`~repro.core.isa.Program`.

The backends are thin executors of the resulting plan —
``repro.core.ref.execute_plan`` (oracle), ``repro.kernels.engine
.execute_plan`` (Pallas), ``repro.core.halo.execute_plan`` (shard_map)
and ``repro.core.vm.execute_plan`` (SPU VM) — which preserves the f64
bit-identity matrix *by construction*: the pinned accumulation order
(``ref.tap_sum`` walking the factorization recorded on the plan) lives
in exactly one place.

Lowering goes through a **process-wide LRU plan cache**
(:data:`PLAN_CACHE`) keyed on ``(spec, shape, dtype, backend, sweeps,
tile request, interpret, mesh fingerprint)`` — the spec key includes
boundary and structure.  Constructing a second engine, or serving a
repeat shape, therefore costs zero re-lowers and zero autotune sweeps;
the cache exposes hit/miss/lower/autotune counters so tests and the
serving front-end (:mod:`repro.serve.stencil`) can pin that claim.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import threading
from collections import OrderedDict
from typing import Literal, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from . import perfmodel as _pm
from . import trace as _trace
from .isa import assemble_any
from .stencil import (Factorization, StencilPipeline, StencilSpec, as_stages,
                      factor_taps, fusable)

Backend = Literal["ref", "pallas", "vm"]

#: The execution layers a plan can target.  ``"ref"`` is the jnp oracle
#: chain (the numpy oracle shares its pinned order), ``"pallas"`` the
#: fused TPU (Mosaic) kernel — interpret mode on CPU hosts, so the whole
#: correctness matrix runs in CI — and ``"vm"`` the software SPU.  A
#: plan with a mesh fingerprint executes through the distributed halo
#: path with shard-local ``ref``/``pallas`` compute.
BACKENDS = ("ref", "pallas", "vm")

#: The backends that lower to fused pallas kernels (and therefore carry
#: a resolved tile, a ghost strategy chosen by :func:`ghost_strategy_for`
#: and a VMEM feasibility bound).
KERNEL_BACKENDS = ("pallas",)

#: Boundary-ghost strategies a plan can select (the *decision* lives
#: here; the mechanics stay with their backend):
#:
#: * ``"pad"``          — oracle path: re-extend with ``ref.pad_boundary``
#:                        before every application;
#: * ``"pad-free"``     — Pallas: each tile's window is DMA'd straight
#:                        from the unpadded grid + in-kernel ghost
#:                        restoration;
#: * ``"padded-window"`` — Pallas fallback: fetch windows from one
#:                        ``pad_boundary`` copy (grids that are not a
#:                        multiple of the tile or tiles shallower than
#:                        the fetch depth; and the distributed
#:                        shard-local kernel, whose window is the
#:                        exchanged halo);
#: * ``"stream"``       — SPU VM: ghost stream elements served per mode
#:                        at access time;
#: * ``"staged"``       — non-fusable pipelines only: execute the chain
#:                        stage by stage through per-stage cached plans
#:                        (each stage re-resolves its own strategy);
#: * ``"stream-from-host"`` — out-of-core: the grid exceeds the device
#:                        budget (``perfmodel.slab_budget_bytes``, env
#:                        ``CASPER_SLAB_BUDGET``), so the plan carries a
#:                        slab decomposition along the outermost axis
#:                        and executes through the host-staging slab
#:                        executor (:mod:`repro.kernels.stream`).
GHOST_STRATEGIES = ("pad", "pad-free", "padded-window", "stream", "staged",
                    "stream-from-host")

#: Halo-exchange strategies for one sharded axis of a distributed plan:
#: ``"zero-fill"`` (plain ``ppermute``; edge devices receive zeros),
#: ``"wrap-ring"`` (periodic: every hop is a wrap-around ring
#: permutation) and ``"edge-fixup"`` (zero-filled exchange, then the
#: out-of-grid ghosts are overwritten locally with the constant fill or
#: the reflect mirror).
EXCHANGE_STRATEGIES = ("zero-fill", "wrap-ring", "edge-fixup")

# Default output tiles per rank: every extent a multiple of the HBM
# layout's granule (``perfmodel.fetch_grain``: 1024 words in rank 1,
# (8, 128) sublanes x lanes in rank >= 2), so the kernel's DMAs stay
# aligned.  This is the lowering-time default when no tile is
# requested; ``repro.kernels.engine`` re-exports it.
DEFAULT_TILES: dict[int, tuple[int, ...]] = {
    1: (1024,),
    2: (32, 256),
    3: (4, 16, 128),
}


def default_tile(ndim: int) -> tuple[int, ...]:
    return DEFAULT_TILES[ndim]


def resolve_interpret(interpret: bool | None) -> bool:
    """``None`` → interpret mode exactly when the default jax backend is
    the CPU (pallas kernels need the chip; the CPU runs the
    interpreter); an explicit bool passes through.  This is the one
    encoding of the policy — ``repro.core.engine`` and
    ``repro.kernels.engine`` re-export it."""
    if interpret is not None:
        return interpret
    return jax.default_backend() == "cpu"


def normalize_tile(spec: StencilSpec,
                   tile: Sequence[int] | int | None) -> tuple[int, ...]:
    """Default / int-promote / validate a tile for ``spec``."""
    if tile is None:
        tile = default_tile(spec.ndim)
    elif isinstance(tile, int):
        tile = (tile,)
    tile = tuple(int(t) for t in tile)
    if len(tile) != spec.ndim:
        raise ValueError(f"tile rank {len(tile)} != spec ndim {spec.ndim}")
    return tile


# ---------------------------------------------------------------------------
# The decisions (single home; backends only consume the answers)
# ---------------------------------------------------------------------------
def exchange_strategy_for(mode: str) -> str:
    """Halo-exchange strategy for one sharded axis under boundary
    ``mode`` — previously an ad-hoc branch inside ``core.halo``:
    ``periodic`` rides a wrap-around ring permutation at equal launch
    count; ``constant``/``reflect`` keep the zero-filled exchange and fix
    the out-of-grid ghosts up locally; ``zero`` falls out of ``ppermute``
    semantics for free."""
    if mode == "periodic":
        return "wrap-ring"
    if mode in ("constant", "reflect"):
        return "edge-fixup"
    if mode != "zero":
        raise ValueError(f"unknown boundary mode {mode!r}")
    return "zero-fill"


def ghost_strategy_for(spec: StencilSpec, shape: Sequence[int],
                       itemsize: int, sweeps: int,
                       tile: Sequence[int] | int | None) -> str:
    """Pad-free vs padded-window decision for the single-device kernel
    backends.

    The pad-free kernel DMAs each tile's window straight from the
    unpadded grid, with the ghost slabs wrapped around the grid edge
    (:func:`repro.kernels.engine._fetch_pieces`).  That needs every grid
    extent to be a multiple of its tile, and every tile to be at least
    the fetch depth — the ``sweeps*halo`` ghost layers rounded up to the
    HBM granule (:func:`repro.core.perfmodel.fetch_halo`) — so that no
    ghost slab straddles the wrap.  Anything else falls back to the
    padded window, which produces bitwise-identical results.

    Also accepts a fusable :class:`~repro.core.stencil.StencilPipeline`:
    its ``halo`` is the per-dim sum of stage radii, so the same rule
    applies verbatim to the chain's widened window.
    """
    tile = normalize_tile(spec, tile)
    deep = tuple(sweeps * h for h in spec.halo)
    if _pm.pad_free_fetch(tuple(shape), tile, deep, itemsize):
        return "pad-free"
    return "padded-window"


# ---------------------------------------------------------------------------
# The plan
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class ExecutionPlan:
    """Everything a backend needs to execute one fused block of
    ``sweeps`` stencil applications — resolved once at lowering time.

    Plans are frozen, hashable (they key the process-wide jitted-runner
    caches) and produced only by :func:`lower`, which memoizes them in
    :data:`PLAN_CACHE`.
    """

    spec: StencilSpec | StencilPipeline
    shape: tuple[int, ...]              # global grid shape
    dtype: str                          # canonical dtype name
    backend: str                        # one of BACKENDS
    sweeps: int
    interpret: bool                     # resolved (pallas interpret mode)
    tile: tuple[int, ...] | None        # resolved output tile (pallas only)
    tile_request: object                # what was asked: "auto"/tuple/None
    ghost_strategy: str                 # one of GHOST_STRATEGIES
    halo: tuple[int, ...]               # per application (pipelines: sum)
    deep_halo: tuple[int, ...]          # sweeps * halo, per dim
    factorization: Factorization | None  # pinned f64 order (None: pipeline —
                                         # each stage keeps its own)
    boundary_mode: str                  # pipelines: stage 0 (initial ext.)
    boundary_value: float
    program: object                     # assembled Program / PipelineProgram
    mesh: object | None = None          # jax Mesh for distributed plans
    grid_axes: tuple | None = None      # mesh axis name per grid dim
    exchange: tuple | None = None       # per-dim exchange strategy / None
    shard_shape: tuple[int, ...] | None = None
    mesh_fingerprint: tuple | None = None
    fused: bool = True                  # False: non-fusable pipeline —
                                        # execute stage plans in sequence
    slabs: tuple[tuple[int, int], ...] | None = None
                                        # stream-from-host: (start, stop)
                                        # outermost-axis slab cover
    slab_overlap: int | None = None     # stream-from-host: deep_halo[0]
    slab_budget: int | None = None      # device budget (bytes) the slab
                                        # decision was evaluated against
                                        # (single-device ref/pallas only)

    @property
    def stream_plan(self):
        """The assembled stream plan (``program.plan``)."""
        return self.program.plan

    @property
    def is_distributed(self) -> bool:
        return self.mesh is not None

    @property
    def streams_from_host(self) -> bool:
        """True when this plan executes out-of-core by slab streaming."""
        return self.ghost_strategy == "stream-from-host"

    @property
    def needs_host_streaming(self) -> bool:
        """True when execution must stay on the eager host-staging path:
        the plan itself streams, or it is a staged pipeline whose
        per-stage plans will (``jax.device_put`` staging cannot be
        traced, so runners route these around their jitted paths)."""
        if self.streams_from_host:
            return True
        if not self.fused and self.slab_budget is not None:
            grid_bytes = math.prod(self.shape) * jnp.dtype(self.dtype).itemsize
            return grid_bytes > self.slab_budget
        return False

    @property
    def blocks_per_scan_step(self) -> int:
        """Fused blocks :func:`run_plan` runs per ``lax.scan`` step: 2
        where the block's kernel reads the scan carry in place — the
        single-device pad-free Pallas kernel, which DMAs its windows
        straight from the carried grid (``pl.ANY``) — else 1.

        With one such block a step, XLA must hand the kernel's fresh
        output back in the carry's buffer while the kernel still reads
        that buffer, so it copies the whole grid after every block.
        With two, the second block's output takes the carry's buffer
        (the carry is dead after the first block) and no copy is made.
        Every other plan keeps one: the distributed kernel reads the
        exchanged window, not the carry, so there is no copy to remove;
        and jnp executors (``ref``) could be fused by XLA across two
        blocks, changing their pinned f64 order."""
        if (self.backend in KERNEL_BACKENDS and not self.is_distributed
                and self.ghost_strategy == "pad-free"):
            return 2
        return 1

    @property
    def is_pipeline(self) -> bool:
        return isinstance(self.spec, StencilPipeline)

    @property
    def stages(self) -> tuple[StencilSpec, ...]:
        """The stage chain: the pipeline's stages, or ``(spec,)``."""
        return as_stages(self.spec)

    def stage_plan(self, k: int) -> "ExecutionPlan":
        """The single-sweep plan of stage ``k`` — same shape/dtype/
        backend/tile request/mesh, lowered through the cache on demand
        (the staged fallback of non-fusable pipelines executes these;
        fused pipelines never need them)."""
        return lower(self.stages[k], self.shape, self.dtype,
                     backend=self.backend, sweeps=1, tile=self.tile_request,
                     mesh=self.mesh, grid_axes=self.grid_axes,
                     interpret=self.interpret)

    def decompose(self, iters: int) -> tuple[int, int]:
        """``iters = q * sweeps + r`` — the one statement of the fused
        iteration decomposition every runner uses."""
        if iters < 0:
            raise ValueError(f"iters must be >= 0, got {iters}")
        return divmod(iters, self.sweeps)

    def remainder(self, r: int) -> "ExecutionPlan":
        """The plan for a narrower fused block of ``r`` sweeps — same
        spec/shape/backend/tile request, lowered through the cache (so a
        remainder never re-runs the autotuner once any engine has seen
        it)."""
        return lower(self.spec, self.shape, self.dtype,
                     backend=self.backend, sweeps=r, tile=self.tile_request,
                     mesh=self.mesh, grid_axes=self.grid_axes,
                     interpret=self.interpret)


# ---------------------------------------------------------------------------
# The process-wide plan cache
# ---------------------------------------------------------------------------
class PlanCache:
    """LRU cache of lowered plans with observable counters.

    ``hits``/``misses`` count key lookups, ``lowers`` the plan
    constructions actually performed (== misses while the cache is large
    enough), ``autotune_calls`` the lowering-initiated tile autotunes,
    ``evictions`` the LRU drops.  ``stats()`` snapshots everything; the
    serving front-end reports the per-batch delta as its cache-hit rate.
    """

    def __init__(self, maxsize: int = 512):
        self.maxsize = maxsize
        self._store: OrderedDict = OrderedDict()
        self._lock = threading.RLock()
        self.hits = 0
        self.misses = 0
        self.lowers = 0
        self.autotune_calls = 0
        self.evictions = 0

    def __len__(self) -> int:
        return len(self._store)

    def get(self, key):
        with self._lock:
            if key in self._store:
                self._store.move_to_end(key)
                self.hits += 1
                return self._store[key]
            self.misses += 1
            return None

    def put(self, key, plan) -> None:
        with self._lock:
            self._store[key] = plan
            self._store.move_to_end(key)
            while len(self._store) > self.maxsize:
                self._store.popitem(last=False)
                self.evictions += 1

    def get_or_lower(self, key, factory):
        """Atomic miss → lower → insert: the whole sequence (including
        the counter updates and any autotune the factory runs) holds the
        cache lock, so two threads racing on the same novel key cannot
        double-lower or lose counter increments (the RLock keeps nested
        lowering from the factory safe).

        Every freshly lowered plan is statically verified before it
        enters the cache (``repro.analysis``, layer 1): strict mode
        raises — and the offending plan is never cached — while the
        default mode warns.  A cache hit re-runs zero analyses (the
        verifier caches its report per plan)."""
        with self._lock:
            hit = self.get(key)
            if hit is not None:
                return hit
            self.lowers += 1
            with _trace.span(_trace.LOWER, event=True):
                plan = factory()
                _verify_new_plan(plan)
            self.put(key, plan)
            return plan

    def keys(self):
        """Current keys, least- to most-recently used."""
        with self._lock:
            return list(self._store)

    def plans(self):
        """Current plans, least- to most-recently used (no counter
        moves)."""
        with self._lock:
            return list(self._store.values())

    def stats(self) -> dict:
        with self._lock:
            total = self.hits + self.misses
            return {
                "size": len(self._store),
                "maxsize": self.maxsize,
                "hits": self.hits,
                "misses": self.misses,
                "lowers": self.lowers,
                "autotune_calls": self.autotune_calls,
                "evictions": self.evictions,
                "hit_rate": self.hits / total if total else 0.0,
            }

    def clear(self) -> None:
        with self._lock:
            self._store.clear()
            self.hits = self.misses = self.lowers = 0
            self.autotune_calls = self.evictions = 0


def _verify_new_plan(plan) -> None:
    """Layer-1 static verification of a freshly lowered plan (see
    :mod:`repro.analysis`).  Lazy import: ``analysis`` imports this
    module for its constants and decision functions, and lowering must
    stay importable without the analysis package being touched."""
    from repro import analysis  # lazy: avoids the import cycle
    with _trace.span(_trace.VERIFY, event=True):
        analysis.verify_and_record(plan)


#: The process-wide plan cache: one per process, shared by every engine,
#: every ``distributed_stencil_fn`` and the serving front-end.
PLAN_CACHE = PlanCache()


def plan_cache_stats() -> dict:
    return PLAN_CACHE.stats()


def canonical_tile_request(tile) -> object:
    """Hashable canonical form of a tile request: ``"auto"``, ``None``
    or a tuple of ints."""
    if tile is None or tile == "auto":
        return tile
    if isinstance(tile, int):
        return (int(tile),)
    return tuple(int(t) for t in tile)


def mesh_fingerprint(mesh, grid_axes) -> tuple | None:
    """Hashable identity of a mesh placement: axis names, per-axis
    sizes, the exact device assignment (ids in mesh order — two meshes
    over different devices, or the same devices in a different order,
    must NOT share plans: the plan pins its ``Mesh`` object) and the
    grid-dim → axis assignment."""
    if mesh is None:
        return None
    devices = tuple(d.id for d in mesh.devices.flat)
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape), devices,
            tuple(grid_axes) if grid_axes is not None else None)


def plan_key(spec: StencilSpec, shape, dtype, backend: str, sweeps: int,
             tile, interpret: bool, mesh=None, grid_axes=None) -> tuple:
    """The plan-cache key.  Includes everything lowering depends on —
    the full spec (boundary + structure participate via spec equality),
    shape, dtype, backend, sweeps, the tile *request*, the mesh
    fingerprint and the slab-streaming budget (``CASPER_SLAB_BUDGET``
    changes the stream-from-host decision, so forced-budget plans must
    never collide with default-budget ones)."""
    return (spec, tuple(int(n) for n in shape), jnp.dtype(dtype).name,
            backend, int(sweeps), canonical_tile_request(tile),
            bool(interpret), mesh_fingerprint(mesh, grid_axes),
            _pm.slab_budget_bytes())


# ---------------------------------------------------------------------------
# Lowering
# ---------------------------------------------------------------------------
def lower(spec: StencilSpec, shape: Sequence[int], dtype, *,
          backend: Backend = "ref", sweeps: int = 1,
          tile: Sequence[int] | int | Literal["auto"] | None = None,
          mesh=None, grid_axes: Sequence[str | None] | None = None,
          interpret: bool | None = None) -> ExecutionPlan:
    """Lower ``(spec, shape, dtype, …)`` to an :class:`ExecutionPlan`,
    through the process-wide :data:`PLAN_CACHE`.

    Safe to call inside a jit trace: every input is static.  ``mesh`` +
    ``grid_axes`` request a distributed plan (tile autotuning then runs
    on the shard shape and per-axis exchange strategies are resolved).
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; expected one of "
                         f"{BACKENDS}")
    if sweeps < 1:
        raise ValueError(f"sweeps must be >= 1, got {sweeps}")
    shape = tuple(int(n) for n in shape)
    if len(shape) != spec.ndim:
        raise ValueError(f"shape rank {len(shape)} != spec ndim {spec.ndim}")
    if (mesh is None) != (grid_axes is None):
        raise ValueError("mesh and grid_axes must be passed together")
    if grid_axes is not None and len(grid_axes) != spec.ndim:
        raise ValueError("grid_axes must have one entry per grid dim")
    interp = resolve_interpret(interpret)
    tile_req = canonical_tile_request(tile)
    axes = tuple(grid_axes) if grid_axes is not None else None

    key = plan_key(spec, shape, dtype, backend, sweeps, tile_req, interp,
                   mesh, axes)
    return PLAN_CACHE.get_or_lower(
        key, lambda: _lower_uncached(spec, shape, jnp.dtype(dtype), backend,
                                     sweeps, tile_req, mesh, axes, interp,
                                     mesh_fingerprint(mesh, axes)))


def _slab_decomposition(shape, deep, itemsize):
    """The out-of-core decision for a single-device ref/pallas plan:
    ``(budget, slabs, overlap)``.  ``slabs`` is ``None`` while the whole
    grid fits the device budget; otherwise it is an exact contiguous
    cover of the outermost axis in equal slabs (short last slab for
    non-divisible extents), each sized so the double-buffered streaming
    resident set (``perfmodel.slab_resident_bytes``) fits the budget,
    and ``overlap = deep[0]`` — the slab boundary is a ``sweeps*halo``
    deep halo against host memory, PR 2's arithmetic verbatim."""
    budget = _pm.slab_budget_bytes()
    if math.prod(shape) * itemsize <= budget:
        return budget, None, None
    overlap = deep[0]
    length = _pm.max_slab_len(shape, deep, itemsize, budget)
    slabs = tuple((s, min(s + length, shape[0]))
                  for s in range(0, shape[0], length))
    return budget, slabs, overlap


def _shard_shape(shape, mesh, axes) -> tuple[int, ...]:
    out = []
    for d, n in enumerate(shape):
        name = axes[d] if d < len(axes) else None
        size = mesh.shape[name] if name is not None else 1
        if n % size:
            raise ValueError(
                f"grid dim {d} ({n}) not divisible by mesh axis "
                f"{name!r} ({size})")
        out.append(n // size)
    return tuple(out)


def _lower_uncached(spec, shape, dtype, backend, sweeps, tile_req, mesh,
                    axes, interp, fingerprint) -> ExecutionPlan:
    """Lower a spec or a pipeline to one fused plan.  A spec is the
    one-stage chain :func:`~repro.core.stencil.as_stages` of itself, so
    one ladder serves both: the fetched halo per application is the
    per-dim **sum of the stage radii** (``plan.halo``; a spec's own
    radius), widened ``sweeps``-deep (``deep_halo = sweeps * halo``),
    and the window's initial ghost extension is stage 0's mode (between
    stages the fused core restores ghosts per the consuming stage's
    own mode).  Intermediate stage fields live in the VMEM window and
    never round-trip HBM.

    A chain mixing periodic with non-periodic stages cannot restore
    between-stage ghosts tile-locally (:func:`~repro.core.stencil
    .fusable`): the plan is then marked ``fused=False`` with ghost
    strategy ``"staged"`` and :func:`execute` runs the per-stage cached
    plans in sequence instead.

    Counters (``lowers``, ``autotune_calls``) update under the cache
    lock: this only runs from :meth:`PlanCache.get_or_lower`.
    """
    halo = spec.halo
    deep = tuple(sweeps * h for h in halo)
    fused = fusable(spec)
    mode, value = spec.boundary_mode, spec.boundary_value

    shard_shape = exchange = None
    if mesh is not None:
        shard_shape = _shard_shape(shape, mesh, axes)
        if fused:
            exchange = tuple(
                exchange_strategy_for(mode) if axes[d] is not None else None
                for d in range(spec.ndim))

    slab_budget = slabs = slab_overlap = None
    if mesh is None and backend in ("ref",) + KERNEL_BACKENDS:
        if fused:
            slab_budget, slabs, slab_overlap = _slab_decomposition(
                shape, deep, dtype.itemsize)
        else:
            # staged chains stream per stage; record the budget so
            # runners know to stay on the eager host-staging path
            slab_budget = _pm.slab_budget_bytes()

    resolved_tile = None
    ghost = "pad" if fused else "staged"        # oracle default / staged
    if not fused:
        pass                                    # stage plans decide
    elif backend in KERNEL_BACKENDS:
        tune_shape = shard_shape if shard_shape is not None else shape
        if slabs is not None:                   # tune for the slab window
            tune_shape = (slabs[0][1] - slabs[0][0],) + shape[1:]
        if tile_req == "auto":
            from repro.kernels import tune      # lazy: optional dep
            PLAN_CACHE.autotune_calls += 1
            with _trace.span(_trace.AUTOTUNE, event=True):
                resolved_tile = tune.autotune(spec, tune_shape,
                                              sweeps=sweeps,
                                              itemsize=dtype.itemsize).tile
        else:
            resolved_tile = normalize_tile(spec, tile_req)
        if mesh is not None:
            # the shard-local kernel always runs on the exchanged
            # (already ghost-extended) window
            ghost = "padded-window"
        elif slabs is not None:
            ghost = "stream-from-host"
        else:
            ghost = ghost_strategy_for(spec, shape, dtype.itemsize, sweeps,
                                       resolved_tile)
    elif backend == "vm":
        ghost = "stream"
    elif slabs is not None:                     # ref oracle, over budget
        ghost = "stream-from-host"

    return ExecutionPlan(
        spec=spec, shape=shape, dtype=dtype.name, backend=backend,
        sweeps=sweeps, interpret=interp, tile=resolved_tile,
        tile_request=tile_req, ghost_strategy=ghost, halo=halo,
        deep_halo=deep,
        factorization=(factor_taps(spec) if isinstance(spec, StencilSpec)
                       else None),
        boundary_mode=mode, boundary_value=value,
        program=assemble_any(spec), mesh=mesh, grid_axes=axes,
        exchange=exchange, shard_shape=shard_shape,
        mesh_fingerprint=fingerprint, fused=fused, slabs=slabs,
        slab_overlap=slab_overlap, slab_budget=slab_budget)


# ---------------------------------------------------------------------------
# Execution: thin dispatch to the backend executors
# ---------------------------------------------------------------------------
def execute(plan: ExecutionPlan, grid):
    """One fused block — ``plan.sweeps`` stencil applications — on the
    plan's backend.  Traceable under jit/vmap (except ``"vm"``, which is
    numpy, and ``"stream-from-host"``, which stages slabs through
    ``jax.device_put``).  A non-fusable pipeline plan (``fused=False``)
    executes its stage chain through per-stage cached plans instead —
    same chained semantics, per-stage HBM traffic."""
    if plan.streams_from_host:
        from repro.kernels import stream as _stream     # lazy: optional dep
        return _stream.execute_plan(plan, grid)
    if not plan.fused:
        out = grid
        for _ in range(plan.sweeps):
            for k in range(len(plan.stages)):
                out = execute(plan.stage_plan(k), out)
        return out
    if plan.is_distributed:
        from . import halo as _halo
        return _halo.execute_plan(plan, grid)
    if plan.backend == "ref":
        from . import ref as _ref
        return _ref.execute_plan(plan, grid)
    if plan.backend == "pallas":
        from repro.kernels import engine as _keng   # lazy: optional dep
        return _keng.execute_plan(plan, grid)
    if plan.backend == "vm":
        from . import vm as _vm
        return _vm.execute_plan(plan, grid)[0]
    raise ValueError(f"unknown backend {plan.backend!r}")


def run_plan(plan: ExecutionPlan, grid, iters: int):
    """``iters`` total applications under ``plan``: ``q`` fused blocks
    rolled into one ``lax.scan`` plus one narrower remainder block whose
    plan comes from the cache — the one statement of the fused iteration
    loop shared by the engine, the distributed path and the serving
    front-end.

    Pad-free Pallas plans run two fused blocks per scan step
    (``plan.blocks_per_scan_step``; an odd ``q`` runs its last block
    after the loop): their kernel reads the carried grid in place, and
    with one block a step XLA copies the whole grid after every block to
    give the output the carry's buffer.  The result is bitwise that of
    ``q`` chained :func:`execute` calls either way.

    ``iters == 0`` returns a *defensive copy* of the input, never the
    input itself: the slab executor donates device buffers, so a no-op
    result aliasing a caller-held array would be corrupted by the next
    streamed call (regression-tested in tests/test_slabs.py).  Plans on
    the host-staging path (``needs_host_streaming``) run an eager slab
    loop instead of ``lax.scan`` — device staging cannot be traced."""
    q, r = plan.decompose(iters)
    if iters == 0:
        if isinstance(grid, np.ndarray):
            return grid.copy()
        return jnp.array(grid, copy=True)
    if plan.needs_host_streaming:
        from repro.kernels import stream as _stream     # lazy: optional dep
        return _stream.run_plan_streamed(plan, grid, iters)
    out = grid
    if q:
        def body(g, _):
            return execute(plan, g), None
        out, _ = jax.lax.scan(body, out, None, length=q,
                              unroll=plan.blocks_per_scan_step)
    if r:
        out = execute(plan.remainder(r), out)
    return out


def _grid_shape_for(spec: StencilSpec, grid) -> tuple[int, ...]:
    """The per-grid shape to lower for: ``grid`` may carry one leading
    batch dimension (the Pallas engine vmaps over it)."""
    if grid.ndim == spec.ndim + 1:
        return tuple(grid.shape[1:])
    return tuple(grid.shape)


def _may_stream(spec, shape, dtype, backend: str) -> bool:
    """Cheap eager predicate: could lowering pick ``stream-from-host``
    for these inputs?  Lets the runners keep the common (fitting) path
    free of eager plan-cache traffic — they only lower outside the jit
    when the grid actually exceeds the configured budget."""
    return (backend in ("ref",) + KERNEL_BACKENDS
            and math.prod(shape) * jnp.dtype(dtype).itemsize
            > _pm.slab_budget_bytes())


@functools.lru_cache(maxsize=512)
def runner(spec: StencilSpec, backend: str, sweeps: int, tile_req,
           interpret: bool):
    """Process-wide jitted ``run(grid, iters)`` for an engine
    configuration.  Keyed on the canonical lowering inputs, so a second
    :class:`~repro.core.engine.CasperEngine` with identical options
    reuses the *same* jitted callable — zero retraces, zero re-lowers,
    zero autotune sweeps (the plan-cache counters pin this).

    Grids past the slab-streaming budget route around the jitted path to
    the eager host-staging executor (``jax.device_put`` staging cannot
    be traced); fitting grids take the jitted path unchanged.
    """
    @functools.partial(jax.jit, static_argnames=("iters",))
    def run_jit(grid, iters: int):
        plan = lower(spec, _grid_shape_for(spec, grid), grid.dtype,
                     backend=backend, sweeps=sweeps, tile=tile_req,
                     interpret=interpret)
        return run_plan(plan, grid, iters)

    def run(grid, iters: int):
        if _may_stream(spec, _grid_shape_for(spec, grid), grid.dtype,
                       backend):
            plan = lower(spec, _grid_shape_for(spec, grid), grid.dtype,
                         backend=backend, sweeps=sweeps, tile=tile_req,
                         interpret=interpret)
            if plan.needs_host_streaming:
                return run_plan(plan, grid, iters)
        return run_jit(grid, iters=iters)
    return run


@functools.lru_cache(maxsize=512)
def batch_runner(spec: StencilSpec, backend: str, sweeps: int, tile_req,
                 interpret: bool, donate: bool = False):
    """Process-wide jitted ``run(grids, iters)`` over a stacked batch of
    same-shaped grids: one plan lowered for the element shape, one
    vmapped fused call for the whole bucket (the serving front-end's
    execution primitive).  Slab-streamed element shapes fall back to an
    eager per-grid host-streaming loop — the serving front-end reports
    those requests under a distinct stat instead of the bucket path.

    ``donate=True`` donates the stacked input buffer to the fused call
    (off-CPU — the CPU backend cannot alias donated buffers, same policy
    as :mod:`repro.kernels.stream`): the continuous-batching server
    stages each bucket onto the device once and never touches the
    staging buffer again, so the output may reuse it in place."""
    donate_argnums = ((0,) if donate and jax.default_backend() != "cpu"
                      else ())

    @functools.partial(jax.jit, static_argnames=("iters",),
                       donate_argnums=donate_argnums)
    def run_jit(grids, iters: int):
        plan = lower(spec, grids.shape[1:], grids.dtype, backend=backend,
                     sweeps=sweeps, tile=tile_req, interpret=interpret)
        return jax.vmap(lambda g: run_plan(plan, g, iters))(grids)

    def run(grids, iters: int):
        if _may_stream(spec, tuple(grids.shape[1:]), grids.dtype, backend):
            plan = lower(spec, grids.shape[1:], grids.dtype, backend=backend,
                         sweeps=sweeps, tile=tile_req, interpret=interpret)
            if plan.needs_host_streaming:
                return np.stack([np.asarray(run_plan(plan, g, iters))
                                 for g in np.asarray(grids)])
        return run_jit(grids, iters=iters)
    return run


@dataclasses.dataclass(frozen=True)
class BatchHandle:
    """Async-friendly three-phase handle over the jitted batch runner —
    the continuous-batching server's execution primitive
    (:mod:`repro.serve.scheduler`).

    ``stage`` puts one bucket's stacked host grids on the device and
    ``dispatch`` launches the vmapped fused call on a staged buffer;
    both return as soon as the work is *enqueued* (jax async dispatch),
    so the caller can stage bucket ``k+1`` while bucket ``k`` computes —
    the upload/compute overlap proven by the slab-streaming executor
    (:mod:`repro.kernels.stream`), applied to serving buckets.  Only
    ``fetch`` blocks (device sync + one transfer back).

    Dispatch donates the staged buffer (off-CPU): after ``dispatch(s)``
    the buffer ``s`` is consumed and must not be reused.
    """

    spec: StencilSpec | StencilPipeline
    backend: str
    sweeps: int
    tile_request: object
    interpret: bool

    def stage(self, grids: Sequence):
        """Stack one bucket and start its host→device transfer; returns
        the (async) device buffer.  Host arrays stack on the host first —
        one transfer per bucket, not one per request."""
        if all(isinstance(g, np.ndarray) for g in grids):
            return jax.device_put(np.stack(grids))
        return jnp.stack([jnp.asarray(g) for g in grids])

    def dispatch(self, staged, iters: int):
        """Launch the bucket's vmapped fused call on a staged device
        buffer (donated — ``staged`` is consumed); returns the async
        device result."""
        run = batch_runner(self.spec, self.backend, self.sweeps,
                           self.tile_request, self.interpret, True)
        return run(staged, iters=iters)

    def fetch(self, result) -> np.ndarray:
        """Block on the device result and bring it back to the host."""
        return np.asarray(result)


def batch_handle(spec: StencilSpec | StencilPipeline, backend: str,
                 sweeps: int, tile_req, interpret: bool | None
                 ) -> BatchHandle:
    """The :class:`BatchHandle` for one engine configuration (cheap
    value object; the jitted runner behind it is the process-wide
    :func:`batch_runner` cache entry)."""
    return BatchHandle(spec, backend, sweeps,
                       canonical_tile_request(tile_req),
                       resolve_interpret(interpret))


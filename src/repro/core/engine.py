"""CasperEngine: the user-facing stencil runtime.

Composes the pieces the way the paper's API (Table 1) does:

    engine = CasperEngine(jacobi2d(), backend="pallas")
    out    = engine.run(grid, iters=100)        # single host/device
    step   = engine.distributed_fn(mesh, ("sx", "sy"))   # multi-device

The engine is now a thin front over the **ExecutionPlan lowering
pipeline** (:mod:`repro.core.plan`): the first time a grid shape is
seen, ``plan.lower`` resolves — once — the tap factorization, the
boundary-ghost strategy, the (auto)tuned tile, the ``iters = q*sweeps +
r`` decomposition and the assembled SPU program, and memoizes the plan
in the process-wide plan cache.  ``run``/``step`` just execute the plan;
a *second* engine with identical options reuses the same jitted runner
and the same cached plans — zero retraces, zero autotune sweeps (the
cache counters pin this, see ``tests/test_plan.py``).

``sweeps=t`` applies temporal blocking — the Pallas backend fuses ``t``
Jacobi applications per kernel invocation — and ``run(grid, iters)``
decomposes ``iters`` into fused blocks plus an exact remainder whose
narrower plan also comes from the plan cache (never a fresh autotune at
trace time).  ``tile="auto"`` resolves through the autotuner inside
``plan.lower`` and nowhere else.

Boundary handling rides on the spec: construct the engine with e.g.
``CasperEngine(jacobi2d().with_boundary("periodic"))`` and every path —
``run``, ``step``, ``distributed_fn`` — serves edge taps per that mode
(zero / constant(c) / periodic / reflect), f64 bit-identically to the
oracle.  The engine is frozen after ``__init__`` (mutating ``sweeps``/
``backend``/``tile``/... raises); build a new engine to change options,
including the boundary.

``spec`` may also be a :class:`~repro.core.stencil.StencilPipeline` — a
DAG chain of stages lowered into one fused plan (intermediate stage
fields never round-trip HBM; see docs/pipelines.md).  Every engine
surface (``run`` / ``step`` / ``distributed_fn`` / ``plan_for``) accepts
it transparently; ``engine.program`` is then the per-stage
:class:`~repro.core.isa.PipelineProgram`.

The assembled Casper program (ISA) is available as ``engine.program`` and
is what `initStencilcode` would broadcast to the SPUs.
"""
from __future__ import annotations

import functools
from typing import Literal, Sequence

import jax

from . import plan as _plan
from . import trace as _trace
from .halo import distributed_stencil_fn
from .isa import assemble_any
from .plan import resolve_interpret  # canonical home is core.plan
from .segment import SegmentConfig
from .stencil import StencilPipeline, StencilSpec

Backend = Literal["ref", "pallas"]


class CasperEngine:
    def __init__(
        self,
        spec: StencilSpec | StencilPipeline,
        backend: Backend = "ref",
        segment: SegmentConfig | None = None,
        interpret: bool | None = None,
        sweeps: int = 1,
        tile: Sequence[int] | Literal["auto"] | None = None,
    ):
        if sweeps < 1:
            raise ValueError(f"sweeps must be >= 1, got {sweeps}")
        if backend not in ("ref",) + _plan.KERNEL_BACKENDS:
            raise ValueError(f"unknown backend {backend!r}")
        self.spec = spec
        self.backend = backend
        self.segment = segment or SegmentConfig()
        # None -> auto-detect: interpret kernels on CPU, compile on
        # the chip.
        self.interpret = resolve_interpret(interpret)
        self.sweeps = sweeps
        self.tile = tile
        # Pipelines assemble to a PipelineProgram (one Program per stage).
        self.program = assemble_any(spec)
        self._frozen = True

    def __setattr__(self, name, value):
        # run() delegates to a process-wide jitted runner keyed on the
        # init-time sweeps/backend/tile; mutating them afterwards would
        # silently keep executing stale fused blocks.  The engine is
        # therefore frozen: construct a new engine to change options.
        if getattr(self, "_frozen", False):
            raise AttributeError(
                f"CasperEngine is frozen; cannot set {name!r} after init — "
                "construct a new engine instead")
        super().__setattr__(name, value)

    def plan_for(self, shape: Sequence[int], dtype,
                 sweeps: int | None = None) -> _plan.ExecutionPlan:
        """The (cached) execution plan this engine uses for ``shape``."""
        return _plan.lower(
            self.spec, shape, dtype, backend=self.backend,
            sweeps=self.sweeps if sweeps is None else sweeps,
            tile=self.tile, interpret=self.interpret)

    def step(self, grid: jax.Array) -> jax.Array:
        """One fused block: ``self.sweeps`` stencil applications."""
        return _plan.execute(
            self.plan_for(_plan._grid_shape_for(self.spec, grid),
                          grid.dtype), grid)

    @functools.cached_property
    def _run_jit(self):
        # Process-wide: a second engine with identical options gets the
        # *same* jitted callable (warm XLA cache, zero retraces).
        return _plan.runner(self.spec, self.backend, self.sweeps,
                            _plan.canonical_tile_request(self.tile),
                            self.interpret)

    def run(self, grid: jax.Array, iters: int = 1) -> jax.Array:
        """``iters`` total stencil applications (fused ``sweeps`` at a
        time; any remainder runs as one narrower fused call whose plan
        comes from the plan cache).  A grid past the device-memory
        budget (``CASPER_SLAB_BUDGET``) transparently runs out-of-core:
        the shared runner routes it through the slab-streaming executor
        (``kernels.stream``) and returns a host array.  The call is the
        profiler span ``casper.run`` (:mod:`repro.core.trace`)."""
        with _trace.span(_trace.RUN):
            return self._run_jit(grid, iters=iters)

    def analyze(self, shape: Sequence[int], dtype=None, *,
                sweeps: int | None = None, lint: bool = True):
        """Static analysis report for the plan this engine would use on
        ``shape``: the layer-1 invariant catalog (already run — and
        cached — when the plan was lowered) plus, when ``lint``, the
        layer-2 jaxpr/HLO lint (de-specialization, dtype contract, FMA
        contraction, HBM round-trips).  See :mod:`repro.analysis` and
        docs/analysis.md."""
        from repro import analysis  # lazy: keep engine import-light
        if dtype is None:
            dtype = jax.numpy.float32
        plan = self.plan_for(shape, dtype, sweeps=sweeps)
        return analysis.analyze_plan(plan, lint=lint)

    _INHERIT = object()   # tile sentinel: None is itself a legal tile value

    def distributed_fn(self, mesh, grid_axes: Sequence[str | None],
                       iters: int = 1, *,
                       sweeps: int | None = None,
                       backend: Backend | None = None,
                       tile=_INHERIT):
        """Jitted multi-device function on ``mesh`` (see core.halo).

        Inherits the engine's ``sweeps``/``backend``/``tile`` unless
        overridden, so temporal blocking (deep halo exchange + fused
        shard-local sweeps) and the Pallas backend apply in the
        distributed path exactly as in :meth:`run`; ``iters`` decomposes
        as ``q*sweeps + r`` the same way (both through the plan's
        ``decompose``).  Each call of the returned function is the
        profiler span ``casper.run``, as :meth:`run` is; its ``lower``
        is the jitted function's, for ahead-of-time callers.
        """
        fn = distributed_stencil_fn(
            self.spec, mesh, grid_axes, iters,
            sweeps=self.sweeps if sweeps is None else sweeps,
            backend=self.backend if backend is None else backend,
            tile=self.tile if tile is CasperEngine._INHERIT else tile,
            interpret=self.interpret)

        @functools.wraps(fn)
        def run(grid: jax.Array) -> jax.Array:
            with _trace.span(_trace.RUN):
                return fn(grid)
        run.lower = fn.lower
        return run

    # Casper API surface (Table 1), as thin documentation shims -------------
    def init_stencil_segment(self, size_bytes: int) -> SegmentConfig:
        return SegmentConfig(mapping="blocked")

    def init_stencilcode(self) -> tuple[int, ...]:
        return self.program.words

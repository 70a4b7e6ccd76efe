"""Pallas TPU kernels (validated with interpret=True on CPU).

The per-rank stencil kernels of the seed (`stencil1d/2d/3d`) are
deprecated shims over the plan-driven unified engine: new code should
call ``engine.stencil_apply(spec, grid, tile=..., sweeps=...)``, go
through :class:`repro.core.engine.CasperEngine`, or lower an
:class:`repro.core.plan.ExecutionPlan` directly and hand it to
``engine.execute_plan``.
"""
import warnings

from . import engine, ops, ref, stream, tune
from .engine import (stencil_apply, stencil_sweep, stencil_window_sweep,
                     run_sweeps, hbm_traffic, execute_plan)
from .swa import sliding_window_attention, swa_ref
from .tune import autotune, autotune_measured


def _legacy_rank_shim(rank: int, spec, grid, tile, interpret):
    warnings.warn(
        f"repro.kernels.stencil{rank}d is deprecated; use "
        "kernels.engine.stencil_apply / CasperEngine (the per-rank seed "
        "kernels were folded into the plan-driven engine)",
        DeprecationWarning, stacklevel=3)
    from repro.core import plan as _plan
    p = _plan.lower(spec, grid.shape, grid.dtype, backend="pallas",
                    tile=tile, interpret=interpret)
    return _plan.execute(p, grid)


def stencil1d(spec, grid, tile: int = 1024, interpret: bool | None = None):
    """DEPRECATED compat shim for the seed's 1-D kernel (one sweep)."""
    return _legacy_rank_shim(1, spec, grid, (tile,), interpret)


def stencil2d(spec, grid, tile=(32, 256), interpret: bool | None = None):
    """DEPRECATED compat shim for the seed's 2-D kernel (one sweep)."""
    return _legacy_rank_shim(2, spec, grid, tile, interpret)


def stencil3d(spec, grid, tile=(4, 16, 128), interpret: bool | None = None):
    """DEPRECATED compat shim for the seed's 3-D kernel (one sweep)."""
    return _legacy_rank_shim(3, spec, grid, tile, interpret)


__all__ = ["engine", "ops", "ref", "stream", "tune",
           "stencil_apply", "stencil_sweep", "stencil_window_sweep",
           "run_sweeps", "hbm_traffic", "execute_plan",
           "autotune", "autotune_measured",
           "stencil1d", "stencil2d", "stencil3d",
           "sliding_window_attention", "swa_ref"]

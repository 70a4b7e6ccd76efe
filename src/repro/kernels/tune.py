"""Tile-shape autotuner for the unified stencil engine.

Ranks candidate output tiles with the first-order TPU cost models in
:mod:`repro.core.perfmodel` (``pallas_tile_cost``): memory traffic of
the aligned fetch windows vs compute roofline, VMEM feasibility,
alignment padding and per-step sequencing overhead.  The analytic pass
is free, so it runs for every (spec, shape, sweeps) the engine sees,
the spec being a :class:`StencilSpec` or a fused pipeline (a spec is
the one-stage chain of itself);
``autotune_measured`` additionally wall-clocks the top analytic
candidates on the real array (interpret mode on CPU, compiled on the
chip) and re-ranks by measurement.

Every candidate extent is a multiple of the HBM layout's granule
(:func:`repro.core.perfmodel.fetch_grain`: 1024 words in rank 1, 8
sublanes x 128 lanes for 32-bit data in rank >= 2), so the kernel's
window DMAs stay aligned.  See docs/kernels.md.

Each stage's boundary mode participates in the ranking (``reflect``
charges the between-sweep ghost re-mirroring gather) and so does its
tap *structure*: the compute term uses the factored per-point flop
count (``structured_flops_per_point()``) and the feasibility check
charges one live window-sized intermediate per factored term.  All of
it enters the cache key: ``autotune`` is memoized on the full spec
(stage order, boundary and structure included).

``autotune_measured`` results can persist across processes: point
``CASPER_TUNE_CACHE`` at a directory and each measured tune is stored
as one JSON file keyed (sha256) like the plan cache — full spec, shape,
dtype, sweeps and measurement config.
Hit/miss/store counters (:data:`TUNE_DISK_CACHE`) are pinned by tests
the same way ``plan.PLAN_CACHE``'s are.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import json
import math
import os
import time
from typing import Sequence

from repro.core import perfmodel as pm
from repro.core.stencil import StencilSpec

#: Output tiles the autotuner ranks for every kernel.
CANDIDATE_TILES: dict[int, tuple[tuple[int, ...], ...]] = {
    1: ((1024,), (2048,), (4096,), (8192,), (16384,)),
    2: ((8, 128), (8, 256), (16, 128), (16, 256), (32, 128), (32, 256),
        (32, 512), (64, 256), (8, 512)),
    3: ((2, 16, 128), (4, 8, 128), (4, 16, 128), (8, 16, 128),
        (4, 16, 256), (8, 8, 128), (4, 32, 128), (2, 32, 256)),
}

#: Wider tiles, ranked too where Mosaic compiles the kernel body about
#: as fast as at the tiles above (``perfmodel.compiles_quickly``).  On
#: a TPU v5e they amortise the ~1 us fixed cost of a grid step: (128,
#: 1024) ran a 16384^2 jacobi2d block at sweeps=4 in 13.57 ms against
#: 33.16 ms at (32, 512), (8, 32, 256) a 512^3 heat3d block in 16.07
#: against 29.34 ms at (8, 16, 128).  Larger tiles ran faster still but
#: compiled 1.5 s or more longer.
WIDE_TILES: dict[int, tuple[tuple[int, ...], ...]] = {
    2: ((64, 512), (64, 1024), (128, 512), (128, 1024)),
    3: ((8, 32, 256),),
}


def candidate_tiles(ndim: int,
                    shape: Sequence[int] | None = None,
                    itemsize: int = 4, spec=None,
                    sweeps: int = 1) -> tuple[tuple[int, ...], ...]:
    """Candidates for ``ndim`` whose extents are multiples of the HBM
    granule for ``itemsize``, dropping tiles absurdly larger than the
    grid (a tile more than 4x the padded extent wastes every lane).
    Given the ``spec`` (or fused pipeline) and ``sweeps`` of the kernel,
    the :data:`WIDE_TILES` whose body compiles quickly join them."""
    tiles = CANDIDATE_TILES[ndim]
    if spec is not None:
        tiles += tuple(t for t in WIDE_TILES.get(ndim, ())
                       if pm.compiles_quickly(spec, t, sweeps))
    grain = pm.fetch_grain(ndim, itemsize)
    cands = tuple(t for t in tiles
                  if all(td % g == 0 for td, g in zip(t, grain)))
    if shape is None:
        return cands
    kept = tuple(t for t in cands
                 if all(td <= 4 * nd for td, nd in zip(t, shape)))
    return kept or cands[:1]


@dataclasses.dataclass(frozen=True)
class TuneResult:
    tile: tuple[int, ...]
    cost_s: float                       # analytic (or measured) seconds
    table: tuple[tuple[tuple[int, ...], float], ...]   # all (tile, cost)
    measured: bool = False

    def as_dict(self) -> dict:
        return {
            "tile": list(self.tile),
            "cost_s": self.cost_s,
            "measured": self.measured,
            "table": [{"tile": list(t), "cost_s": c} for t, c in self.table],
        }


def autotune(spec, shape: tuple[int, ...], sweeps: int = 1,
             itemsize: int = 4) -> TuneResult:
    """Best tile for (spec, shape, sweeps) under the analytic cost
    model, where ``spec`` is a :class:`StencilSpec` or a fused
    :class:`~repro.core.stencil.StencilPipeline` chain.  Memoized on
    the full spec — taps, stage order, boundary and structure all
    participate."""
    return _autotune(spec, tuple(shape), sweeps, itemsize)


@functools.lru_cache(maxsize=512)
def _autotune(spec, shape: tuple[int, ...], sweeps: int,
              itemsize: int) -> TuneResult:
    tiles = candidate_tiles(spec.ndim, shape, itemsize, spec, sweeps)
    cost = functools.partial(pm.pallas_tile_cost, spec, shape,
                             sweeps=sweeps, itemsize=itemsize)
    scored = sorted(((tile, cost(tile)) for tile in tiles),
                    key=lambda tc: tc[1])
    best, best_cost = scored[0]
    if math.isinf(best_cost):
        raise ValueError(
            f"no candidate tile fits VMEM for {spec.name} "
            f"sweeps={sweeps}")
    return TuneResult(best, best_cost, tuple(scored))


# ---------------------------------------------------------------------------
# Measured re-ranking + persistent on-disk cache
# ---------------------------------------------------------------------------
#: Directory for persisted ``autotune_measured`` results.  Unset (the
#: default) disables persistence entirely — measured tunes stay
#: process-local.
TUNE_CACHE_ENV = "CASPER_TUNE_CACHE"


@dataclasses.dataclass
class TuneDiskCacheStats:
    """Counters for the ``CASPER_TUNE_CACHE`` persistent cache, pinned
    by tests exactly like ``plan.PLAN_CACHE``'s: ``hits`` served from
    disk, ``misses`` that ran real measurements, ``stores`` written."""
    hits: int = 0
    misses: int = 0
    stores: int = 0

    def as_dict(self) -> dict:
        return {"hits": self.hits, "misses": self.misses,
                "stores": self.stores}

    def reset(self) -> None:
        self.hits = self.misses = self.stores = 0


TUNE_DISK_CACHE = TuneDiskCacheStats()


def _tune_cache_dir() -> str | None:
    root = os.environ.get(TUNE_CACHE_ENV, "").strip()
    return root or None


def _tune_cache_key(spec, shape, itemsize, sweeps, top_k, reps,
                    interpret) -> str:
    """Content key mirroring the plan cache's: the full spec repr
    (taps + boundary + structure), grid shape, dtype width, sweeps and
    the measurement configuration."""
    payload = repr((spec, tuple(shape), int(itemsize), int(sweeps),
                    int(top_k), int(reps), interpret))
    return hashlib.sha256(payload.encode()).hexdigest()[:40]


def _tune_cache_load(key: str) -> TuneResult | None:
    root = _tune_cache_dir()
    if root is None:
        return None
    try:
        with open(os.path.join(root, key + ".json")) as fh:
            payload = json.load(fh)
        table = tuple((tuple(row["tile"]), float(row["cost_s"]))
                      for row in payload["table"])
        return TuneResult(tuple(payload["tile"]), float(payload["cost_s"]),
                          table, measured=True)
    except (OSError, ValueError, KeyError, TypeError):
        return None          # absent or corrupt entry -> re-measure


def _tune_cache_store(key: str, result: TuneResult) -> None:
    root = _tune_cache_dir()
    if root is None:
        return
    os.makedirs(root, exist_ok=True)
    path = os.path.join(root, key + ".json")
    tmp = path + ".tmp"
    with open(tmp, "w") as fh:
        json.dump(result.as_dict(), fh)
    os.replace(tmp, path)    # atomic: concurrent readers never see partials
    TUNE_DISK_CACHE.stores += 1


def autotune_measured(spec: StencilSpec, grid, sweeps: int = 1,
                      top_k: int = 3, reps: int = 2,
                      interpret: bool | None = None) -> TuneResult:
    """Re-rank the ``top_k`` analytic candidates by wall clock on
    ``grid``.  With ``CASPER_TUNE_CACHE`` set, results persist on disk
    across process restarts (measured tunes are the expensive ones —
    each candidate compiles and runs)."""
    from . import engine  # local import: tune is importable without jax use

    key = _tune_cache_key(spec, grid.shape, grid.dtype.itemsize, sweeps,
                          top_k, reps, interpret)
    if _tune_cache_dir() is not None:
        cached = _tune_cache_load(key)
        if cached is not None:
            TUNE_DISK_CACHE.hits += 1
            return cached
        TUNE_DISK_CACHE.misses += 1

    analytic = autotune(spec, tuple(grid.shape), sweeps=sweeps,
                        itemsize=grid.dtype.itemsize)
    finite = [(t, c) for t, c in analytic.table if math.isfinite(c)]
    timed = []
    for tile, _ in finite[:top_k]:
        fn = functools.partial(engine.stencil_apply, spec, tile=tile,
                               sweeps=sweeps, interpret=interpret)
        fn(grid).block_until_ready()            # warm up / compile
        t0 = time.perf_counter()
        for _ in range(reps):
            out = fn(grid)
        out.block_until_ready()
        timed.append((tile, (time.perf_counter() - t0) / reps))
    timed.sort(key=lambda tc: tc[1])
    best, cost = timed[0]
    result = TuneResult(best, cost, tuple(timed), measured=True)
    _tune_cache_store(key, result)
    return result

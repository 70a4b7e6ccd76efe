"""First-order analytical performance & energy model (paper §7, Table 2).

The paper evaluates Casper in gem5.  We cannot run gem5 here, so this module
re-derives the paper's Figures 10-13 and Tables 5-6 from an explicit
first-order bottleneck model parameterized by the paper's own Table 2
constants.  Every constant is either taken verbatim from the paper, marked
CALIBRATED with its provenance, or (the TPU tile model's) fitted to chip
timings named beside it; `benchmarks/` report model-vs-paper deltas
cell by cell, so the faithfulness of the reproduction is measurable.

Units: seconds, bytes, Joules.  One "sweep" = one stencil application over
the full grid.
"""
from __future__ import annotations

import dataclasses
import math
import os

from .isa import Program, assemble
from .segment import SegmentConfig, remote_fraction
from .stencil import PAPER_STENCILS, DOMAIN_SIZES, StencilSpec, as_stages

# ----------------------------------------------------------------------------
# Machine constants (Table 2 unless noted)
# ----------------------------------------------------------------------------
FREQ = 2.0e9                     # 2 GHz
N_CORES = 16
N_SPUS = 16
N_SLICES = 16
VEC_ELEMS = 8                    # 512-bit SIMD / f64
ELEM = 8                         # bytes per element (double)
LINE = 64                        # bytes per cache line

# Peak f64 FLOP/s of the baseline CPU; the paper's Fig. 1 horizontal line.
CPU_PEAK_FLOPS = 537.6e9
# Fig. 1: all stencils achieve <20% of peak; stencil compute retires at a
# fraction of peak even when not memory-bound. CALIBRATED to Fig. 1 / [42].
CPU_COMPUTE_EFFICIENCY = 0.25

# Cache capacities.
L1_BYTES = 32 * 1024
L2_BYTES = 256 * 1024            # per core
LLC_BYTES = 32 * 1024 * 1024     # shared, 16 slices x 2 MB

# Aggregate sustainable bandwidths (derived from Table 2 port widths).
L2_BW = N_CORES * 64 * FREQ      # 2048 GB/s: 1 load port x 64 B x 16 cores
LLC_CPU_BW = N_SLICES * 64 * FREQ / 2.0   # CPU-side LLC bw; /2 CALIBRATED
                                          # (NoC round-trip + MSHR limits)
LLC_LOCAL_BW = N_SLICES * 64 * FREQ       # SPU-side: local slice, no NoC
DRAM_BW = 4 * 25.6e9             # 4 x DDR4-3200 channels

# Energies (Table 2).
E_CPU_INSTR = 0.08e-9
E_SPU_INSTR = 0.016e-9
E_L1_HIT, E_L1_MISS = 15e-12, 33e-12
E_L2_HIT, E_L2_MISS = 46e-12, 93e-12
E_L3_HIT, E_L3_MISS = 945e-12, 1904e-12
E_DRAM = 160e-9                  # per 64 B read/write

# Remote-slice service penalty for SPU loads, in cycles of extra occupancy
# per remote vector load (NoC hop + remote slice port contention, partially
# hidden by the 10-entry load queue). CALIBRATED to Table 5 3-D rows.
REMOTE_PENALTY_CYCLES = 10.0

# GPU (Titan V, §7.1/§8.3): 652.8 GB/s HBM2, 7.45 f64 TFLOP/s, 815 mm^2.
GPU_BW = 652.8e9
GPU_PEAK_FLOPS = 7.45e12
GPU_AREA_MM2 = 815.0
GPU_LAUNCH_S = 3.3e-6            # kernel launch + sync floor; CALIBRATED to
                                 # Table 5 GPU L2 rows (~4k cycles @ 1.2 GHz)
GPU_FREQ = 1.2e9                 # for converting Table 5 GPU cycles

# Casper hardware additions (§8.6): 16 SPUs + unaligned-load logic.
CASPER_AREA_MM2 = 16 * 0.146 + 16 * 0.14   # = 4.58 (paper rounds to 4.65)

# PIMS (§8.4): performance bounded by HMC atomic-op throughput [156,157].
# CALIBRATED so cache-resident speedup averages ~5.5x (Fig. 13).
PIMS_ATOMICS_PER_S = 35e9
PIMS_INTERNAL_BW = 320e9         # HMC internal bandwidth for streaming

# Baseline-CPU pathologies reported in §8.1 that a first-order model cannot
# derive: the Blur2D DRAM dataset suffers prefetcher-induced evictions (LLC
# hit rate 2%, 4x more DRAM accesses). Taken from the paper's own analysis.
CPU_DRAM_TRAFFIC_FACTOR = {"blur2d": 4.0}


def _dataset_level(spec: StencilSpec, shape: tuple[int, ...]) -> str:
    n_bytes = 2 * math.prod(shape) * ELEM          # in + out arrays
    if n_bytes <= N_CORES * L2_BYTES:
        return "L2"
    if n_bytes <= LLC_BYTES:
        return "L3"
    return "DRAM"


# ----------------------------------------------------------------------------
# Result record
# ----------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class SweepCost:
    seconds: float
    energy_j: float
    bottleneck: str
    detail: dict

    @property
    def cycles(self) -> float:
        return self.seconds * FREQ


# ----------------------------------------------------------------------------
# Baseline CPU model
# ----------------------------------------------------------------------------
def cpu_sweep(spec: StencilSpec, shape: tuple[int, ...]) -> SweepCost:
    n = math.prod(shape)
    level = _dataset_level(spec, shape)
    flops = 2.0 * spec.n_taps * n
    t_compute = flops / (CPU_PEAK_FLOPS * CPU_COMPUTE_EFFICIENCY)

    # Streaming traffic: read input once, write + write-allocate output.
    traffic = 3.0 * n * ELEM
    times = {"compute": t_compute}
    if level == "L2":
        times["L2"] = traffic / L2_BW
    elif level == "L3":
        times["L2"] = traffic / L2_BW
        times["LLC"] = traffic / LLC_CPU_BW
    else:
        dram_traffic = traffic * CPU_DRAM_TRAFFIC_FACTOR.get(spec.name, 1.0)
        times["L2"] = traffic / L2_BW
        times["LLC"] = traffic / LLC_CPU_BW
        times["DRAM"] = dram_traffic / DRAM_BW
    bottleneck = max(times, key=times.get)
    seconds = times[bottleneck]

    # Energy: per-element L1 work + line-granular traffic down the hierarchy.
    loads_stores = (spec.n_taps + 1) * n
    instrs = 1.4 * loads_stores          # ld/st + MAC + loop overhead mix;
                                         # CALIBRATED to Table 4 CPU rows
    lines = traffic / LINE
    energy = instrs * E_CPU_INSTR + loads_stores * E_L1_HIT
    if level in ("L3", "DRAM"):
        energy += lines * (E_L2_MISS + E_L3_HIT)
    else:
        energy += lines * E_L2_HIT
    if level == "DRAM":
        dram_lines = lines * CPU_DRAM_TRAFFIC_FACTOR.get(spec.name, 1.0)
        energy += dram_lines * (E_L3_MISS - E_L3_HIT) + dram_lines * E_DRAM
    return SweepCost(seconds, energy, bottleneck,
                     {"level": level, "times": times, "instrs": instrs})


# ----------------------------------------------------------------------------
# Casper model
# ----------------------------------------------------------------------------
def casper_sweep(
    spec: StencilSpec,
    shape: tuple[int, ...],
    program: Program | None = None,
    seg: SegmentConfig | None = None,
    unaligned_hw: bool = True,
) -> SweepCost:
    n = math.prod(shape)
    level = _dataset_level(spec, shape)
    program = program or assemble(spec)
    seg = seg or SegmentConfig()
    n_instr = program.n_instrs

    vectors = n / VEC_ELEMS
    loads = program.loads_per_vector()
    vec_loads = loads["with_casper"] if unaligned_hw else loads["without_casper"]

    # Issue/bandwidth term: the SPU pipeline retires one vector op per cycle
    # and its local slice supplies one 64 B window per cycle -> the two rates
    # are matched by construction (§3.1), so cycles/vector = max(instr, loads).
    cyc_per_vec = max(n_instr, vec_loads)

    # Remote-slice accesses (only at block boundaries under the linear hash).
    rf = remote_fraction(spec, shape, seg)
    cyc_per_vec += rf * vec_loads * REMOTE_PENALTY_CYCLES

    t_spu = vectors * cyc_per_vec / (N_SPUS * FREQ)
    times = {"spu": t_spu}
    traffic = 3.0 * n * ELEM
    if level == "DRAM":
        times["DRAM"] = traffic / DRAM_BW
    bottleneck = max(times, key=times.get)
    seconds = times[bottleneck]

    # Energy: SPU instructions + per-element LLC accesses (+ DRAM fills).
    llc_accesses = (n_instr + 1) * n     # every tap load + the output store
    energy = (vectors * n_instr * N_SPUS / N_SPUS) * E_SPU_INSTR \
        + llc_accesses * E_L3_HIT
    if level == "DRAM":
        energy += (traffic / LINE) * (E_L3_MISS - E_L3_HIT + E_DRAM)
    return SweepCost(seconds, energy, bottleneck,
                     {"level": level, "times": times,
                      "remote_fraction": rf, "cyc_per_vec": cyc_per_vec})


# ----------------------------------------------------------------------------
# TPU-side tile cost model (drives the Pallas autotuner, kernels/tune.py)
# ----------------------------------------------------------------------------
# v5e figures. HBM matches repro.roofline.HBM_BW (single source for the
# roofline benches; duplicated here so perfmodel stays import-light).
TPU_HBM_BW = 819e9
TPU_VMEM_BYTES = 16 * 1024 * 1024    # Mosaic's default scoped VMEM limit
# The two constants below are least-squares fits (relative error) of
# pallas_tile_cost's form to one fused call of the pad-free kernel on a
# TPU v5e: jacobi2d 16384^2 and heat3d 512^3, f32, zero boundary, 12
# 2-D and 11 3-D tiles at sweeps=4 plus two tiles each at sweeps=1 and
# 2 (31 timings; residuals -18%..+13%, rms 8%).  See docs/kernels.md.
TPU_VPU_FLOPS_F32 = 1.67e12          # effective rate of the compute term:
                                     # structured flops at VPU-padded
                                     # window points, sweeps and ghost
                                     # restore included
TPU_GRID_STEP_S = 1.02e-6            # fixed cost of one grid step: its
                                     # sequencing, the window's DMA starts
                                     # and waits, the cut and the ghost
                                     # restore
VPU_SUBLANES, VPU_LANES = 8, 128     # f32 min tile (sublane x lane)


# ----------------------------------------------------------------------------
# Out-of-core slab streaming budget (ghost strategy "stream-from-host")
# ----------------------------------------------------------------------------
#: Device-memory capacity a whole grid (plus streaming working set) may
#: occupy before ``plan.lower()`` switches to out-of-core slab
#: streaming.  v5e HBM per chip; "Beyond 16GB" (PAPERS.md) frames the
#: same threshold on GPUs.
TPU_HBM_BYTES = 16 * 1024 ** 3

#: Environment override for the slab-streaming budget (bytes).  Tests,
#: the lint matrix and BENCH_7 force tiny budgets through this knob to
#: exercise streaming on grids that still fit, so the env value is part
#: of the plan-cache key (``plan.plan_key``).
SLAB_BUDGET_ENV = "CASPER_SLAB_BUDGET"


def slab_budget_bytes() -> int:
    """The configured device-memory budget for whole-grid residency:
    :data:`TPU_HBM_BYTES` unless ``CASPER_SLAB_BUDGET`` overrides it."""
    raw = os.environ.get(SLAB_BUDGET_ENV)
    if raw is None:
        return TPU_HBM_BYTES
    budget = int(raw)
    if budget < 1:
        raise ValueError(f"{SLAB_BUDGET_ENV} must be >= 1 byte, got {raw!r}")
    return budget


def _slab_row_bytes(shape: tuple[int, ...], deep_halo: tuple[int, ...],
                    itemsize: int) -> int:
    """Bytes of one outermost-axis row of an uploaded slab window: dims
    1.. ride along whole, ghost-padded ``deep_halo[d]`` on each side."""
    row = itemsize
    for d in range(1, len(shape)):
        row *= shape[d] + 2 * deep_halo[d]
    return row


def slab_resident_bytes(slab_len: int, shape: tuple[int, ...],
                        deep_halo: tuple[int, ...], itemsize: int) -> int:
    """Device bytes resident while one slab computes under the
    double-buffered streaming executor: the slab's fetched window
    (``slab_len + 2*deep_halo[0]`` outermost rows, dims 1..
    ghost-padded), the *next* slab's window uploading behind it, and the
    current output block.  The one statement of the streaming working
    set shared by the lowering decision, the plan verifier and
    BENCH_7."""
    row = _slab_row_bytes(shape, deep_halo, itemsize)
    window_rows = slab_len + 2 * deep_halo[0]
    out_bytes = slab_len * itemsize * math.prod(shape[1:])
    return 2 * window_rows * row + out_bytes


def max_slab_len(shape: tuple[int, ...], deep_halo: tuple[int, ...],
                 itemsize: int, budget: int) -> int:
    """Largest outermost slab length whose streaming resident set fits
    ``budget`` (inverse of :func:`slab_resident_bytes`), clamped to 1 —
    a single-row slab is irreducible, so a budget below even that still
    streams row by row."""
    row = _slab_row_bytes(shape, deep_halo, itemsize)
    out_row = itemsize * math.prod(shape[1:])
    # resident(L) = 2*(L + 2*D0)*row + L*out_row  <=  budget
    length = (budget - 4 * deep_halo[0] * row) // (2 * row + out_row)
    return max(1, min(int(length), int(shape[0])))


def _ceil_to(x: int, grain: int) -> int:
    return -(-x // grain) * grain


def fetch_grain(ndim: int, itemsize: int) -> tuple[int, ...]:
    """Per-dim granule of an HBM array's tiled layout on the TPU: a DMA
    may only start and end on these.  Rank 1 is tiled in runs of 1024
    32-bit words, rank >= 2 in (sublane, lane) tiles of (8, 128) 32-bit
    words — sub-32-bit dtypes pack more rows per sublane — and leading
    dims are untiled."""
    pack = max(1, 4 // itemsize)
    if ndim == 1:
        return (1024 * pack,)
    return (1,) * (ndim - 2) + (VPU_SUBLANES * pack, VPU_LANES)


def fetch_halo(deep: tuple[int, ...], grain: tuple[int, ...]
               ) -> tuple[int, ...]:
    """Ghost layers the kernel fetches per side: the ``sweeps*halo``
    window rounded up to the HBM granule so every DMA stays aligned."""
    return tuple(_ceil_to(w, g) for w, g in zip(deep, grain))


def fetch_window(tile: tuple[int, ...], deep: tuple[int, ...],
                 itemsize: int) -> tuple[int, ...]:
    """Extents of the VMEM buffer one grid step DMAs in: the tile plus
    the aligned fetch halo on both sides."""
    lo = fetch_halo(deep, fetch_grain(len(tile), itemsize))
    return tuple(t + 2 * w for t, w in zip(tile, lo))


def tile_window(tile: tuple[int, ...], halo: tuple[int, ...],
                sweeps: int = 1) -> tuple[int, ...]:
    """Fetched input-window extents of one fused block: ``tile +
    2*sweeps*h`` per dim — the one statement of the temporal-blocking
    window arithmetic shared by the cost model, the kernels and the
    plan verifier (``analysis.verify``)."""
    return tuple(t + 2 * sweeps * h for t, h in zip(tile, halo))


def pad_free_fetch(shape: tuple[int, ...], tile: tuple[int, ...],
                   deep: tuple[int, ...], itemsize: int) -> bool:
    """Whether the pad-free kernel can fetch every window straight from
    the unpadded grid: each extent a multiple of its tile, and each tile
    at least its aligned fetch depth, so no wrapped ghost slab straddles
    the grid edge.  ``plan.ghost_strategy_for`` decides on it."""
    lo = fetch_halo(deep, fetch_grain(len(tile), itemsize))
    return all(n % t == 0 and t >= f for n, t, f in zip(shape, tile, lo))


def _vreg_padded(dims) -> int:
    """Elements of an array of extents ``dims`` padded to whole f32
    vregs: the last two dims round up to (sublane, lane)."""
    dims = list(dims)
    dims[-1] = _ceil_to(dims[-1], VPU_LANES)
    if len(dims) >= 2:
        dims[-2] = _ceil_to(dims[-2], VPU_SUBLANES)
    return math.prod(dims)


def vmem_residency(tile: tuple[int, ...], halo: tuple[int, ...],
                   sweeps: int = 1, itemsize: int = 4,
                   n_terms: int = 1) -> int:
    """Bytes resident in VMEM during one grid step of the fused kernel:
    the aligned DMA buffer (:func:`fetch_window`), the double-buffered
    output block, and the values the kernel body keeps live at the
    accumulation width, each padded to whole vregs: the window, its
    accumulator and one tap temporary, plus one window-sized
    intermediate per extra factored term.

    Three live windows is what Mosaic allocates for the v5e: its scoped
    allocation for the pad-free jacobi2d and heat3d kernels at
    sweeps=4, over tiles from (32, 512) to (256, 2048) and from
    (8, 16, 128) to (32, 32, 512), exceeds the buffers by 1.6 to 3.0
    padded windows, and it refuses (32, 32, 512) (20.97 MiB), which two
    unpadded windows counted at 15.97 MiB."""
    acc_itemsize = max(itemsize, 4)
    deep = tuple(sweeps * h for h in halo)
    window = _vreg_padded(tile_window(tile, halo, sweeps))
    fetch = math.prod(fetch_window(tile, deep, itemsize))
    return (fetch * itemsize + (2 + n_terms) * window * acc_itemsize
            + 2 * math.prod(tile) * itemsize)


def _window_traffic(shape: tuple[int, ...], tile: tuple[int, ...],
                    deep: tuple[int, ...], itemsize: int) -> int:
    """HBM bytes of one fused call: every tile reads its aligned fetch
    window and writes its tile; the padded-window fallback also reads
    the grid once and writes its padded copy once."""
    n_tiles = math.prod(-(-n // t) for n, t in zip(shape, tile))
    traffic = n_tiles * (math.prod(fetch_window(tile, deep, itemsize))
                         + math.prod(tile)) * itemsize
    if not pad_free_fetch(shape, tile, deep, itemsize):
        traffic += (math.prod(shape) + math.prod(
            n + 2 * w for n, w in zip(shape, deep))) * itemsize
    return traffic


def _tile_flops(spec, tile: tuple[int, ...],
                sweeps: int) -> tuple[int, int]:
    """Flops of one grid step of the fused kernel, and of them the
    ``reflect`` re-mirror's, walking the element-layer schedule of
    ``ref.masked_window_pipeline`` over the stage chain of ``spec`` (a
    plain spec is its own one-stage chain): each stage application at its
    shrinking VPU-padded window with its own structured per-point
    count, plus, where the next stage to run is ``reflect``, one
    per-axis gather pass over the intermediate window (the other modes'
    fix-up is a masked select already folded into the tap
    accounting)."""
    stages = as_stages(spec)
    n = len(stages)
    rem = tuple(sweeps * h for h in spec.halo)
    flops = mirror = 0
    for step in range(sweeps * n):
        stage = stages[step % n]
        rem = tuple(r - h for r, h in zip(rem, stage.halo))
        pts = _vreg_padded(t + 2 * r for t, r in zip(tile, rem))
        flops += pts * stage.structured_flops_per_point()
        if (step + 1 < sweeps * n
                and stages[(step + 1) % n].boundary_mode == "reflect"):
            mirror += pts * len(tile)
    return flops + mirror, mirror


#: Largest fused-kernel body, in f32 vreg operations (one grid step's
#: flops over 1024 lanes x sublanes), that :func:`compiles_quickly`
#: admits.  Mosaic unrolls the body per vreg, so its compile time
#: follows the body: on a TPU v5e's host the pad-free jacobi2d and
#: heat3d kernels at sweeps=4 compiled within 1 s of the (32, 512) and
#: (8, 16, 128) kernels' 0.43 and 0.74 s for every tile of at most
#: 9296 vreg operations, and 1.5 s or more longer from 11230 on.
TPU_QUICK_COMPILE_VREG_OPS = 10_000


def compiles_quickly(spec, tile: tuple[int, ...], sweeps: int = 1) -> bool:
    """Whether Mosaic compiles the fused kernel of ``spec`` (a
    :class:`StencilSpec` or a fused pipeline) at ``tile`` about as fast
    as at the narrow tiles: a body of at most
    :data:`TPU_QUICK_COMPILE_VREG_OPS` vreg operations that re-mirrors
    no reflect ghosts between sweeps.  A re-mirroring body compiled 2.6
    to 13 times longer than the same body with zero ghosts (jacobi2d,
    blur2d, heat3d and star33_3d at sweeps=4, compiled for a described
    v5e), far more than its flops account for."""
    flops, mirror = _tile_flops(spec, tile, sweeps)
    return not mirror and flops / 1024 <= TPU_QUICK_COMPILE_VREG_OPS


def pallas_tile_cost(spec, shape: tuple[int, ...],
                     tile: tuple[int, ...], sweeps: int = 1,
                     itemsize: int = 4) -> float:
    """Predicted seconds for ``sweeps`` fused applications of ``spec``
    (a :class:`StencilSpec`, or a fused
    :class:`~repro.core.stencil.StencilPipeline` chain) over ``shape``
    with output block ``tile`` (the kernels/engine.py temporal-blocking
    kernel).  Returns ``inf`` when the VMEM working set cannot fit.

    First-order model of what a grid step does: it waits for its whole
    window DMA, then computes, so time = HBM traffic + VPU compute +
    a fixed cost per grid step (:data:`TPU_GRID_STEP_S`), fitted to the
    chip.  The traffic term charges each tile one window read (halo
    widened to ``sweeps*halo``, ``halo`` the per-dim sum of the stage
    radii) plus one tile write — against the *unpadded* grid; the
    pad-free engine materializes boundary ghosts in-kernel, so no
    host-side pad traffic enters (the removed pad copy is charged to
    the unfused baseline by ``kernels.engine.hbm_traffic``), and a
    chain's intermediates stay in VMEM.  The compute term
    (:func:`_tile_flops`) charges every stage application at its
    shrinking window size, padded up to the VPU (sublane, lane) grain
    so misaligned tiles pay for the lanes they waste.

    The compute term is **structure-aware**: per-point flops come from
    each stage's ``structured_flops_per_point()`` — the factored MAC
    count of separable specs (e.g. 15 tap-ops for ``star33_3d`` instead
    of 33; star/dense compute the plain tap chain and keep their dense
    count) — and each extra computed term of the richest stage holds
    one more live window-sized intermediate, charged to the VMEM
    resident set.  Cheaper compute and the extra resident intermediates
    both shift the autotuner's tile choice for separable specs.

    The boundary mode enters through each stage's ``boundary``: traffic
    is mode-independent (the window is fetched whole either way), but a
    ``reflect`` stage adds one per-axis ghost-re-mirroring gather pass
    over every intermediate window it consumes (the other modes' fix-up
    is a masked select already folded into the tap accounting).
    """
    halo = spec.halo
    n_tiles = math.prod(-(-n // t) for n, t in zip(shape, tile))
    n_terms = max(
        (1 if s.factorization.compute_terms is None
         else len(s.factorization.compute_terms)) for s in as_stages(spec))

    # Resident set: DMA buffer + window + accumulator + output block,
    # plus one live window-sized intermediate per extra factored term.
    vmem = vmem_residency(tile, halo, sweeps, itemsize, n_terms)
    if vmem > TPU_VMEM_BYTES:
        return float("inf")

    traffic = _window_traffic(shape, tile, tuple(sweeps * h for h in halo),
                              itemsize)
    t_mem = traffic / TPU_HBM_BW

    t_compute = (_tile_flops(spec, tile, sweeps)[0] * n_tiles
                 / TPU_VPU_FLOPS_F32)
    return t_mem + t_compute + n_tiles * TPU_GRID_STEP_S


# ----------------------------------------------------------------------------
# Serving: bucket-close cost heuristic (continuous-batching scheduler)
# ----------------------------------------------------------------------------
#: Per-bucket host-side dispatch overhead: jitted-call entry, transfer
#: setup, result scatter.  CALIBRATED to the BENCH_5 sequential-vs-
#: batched gap on CPU hosts (each sequential request pays roughly this
#: much on top of its compute; a bucket pays it once).
SERVE_DISPATCH_OVERHEAD_S = 150e-6

#: Slack multiplier on the expected bucket fill time.  Offered load is
#: what the arrival process schedules, not what the admission path
#: achieves: submission jitter (sleep overshoot, GIL hand-offs) makes
#: the realized arrival rate lag the offered rate under saturation, and
#: a timer set to the *nominal* fill time then closes buckets short of
#: the cap.  CALIBRATED against the BENCH_8 saturated sweep, where 3x
#: keeps the hot bucket closing "full" at the achieved (not offered)
#: rate.
SERVE_FILL_SLACK = 3.0


def bucket_close_wait_s(offered_rate_rps: float, max_bucket_size: int,
                        *, deadline_s: float | None = None,
                        dispatch_overhead_s: float =
                        SERVE_DISPATCH_OVERHEAD_S) -> float:
    """How long the admission queue should hold a bucket open before
    closing it short — the ``max_wait`` knob of
    :class:`repro.serve.scheduler.ServeConfig`, derived first-order like
    every other model in this module.

    Holding a bucket open ``w`` seconds gathers ``~rate * w`` more
    same-key requests; each one folded into the bucket saves one
    per-dispatch overhead.  Against that, every queued request pays
    ``w`` of added latency.  Three bounds follow:

    * the expected **fill time** ``max_bucket_size / rate`` (with
      ``SERVE_FILL_SLACK`` headroom for the gap between offered and
      achieved arrival rate) — past it the bucket would have closed
      full anyway, so waiting longer buys nothing;
    * the total overhead a full bucket can amortize,
      ``max_bucket_size * dispatch_overhead_s`` — waiting longer than
      the whole saving is a guaranteed net latency loss;
    * half the SLO budget, when one is given — the bucket wait must
      leave room for staging + compute.

    Floored at one dispatch overhead (a shorter timer just burns wakeups
    without ever coalescing anything).
    """
    if max_bucket_size < 1:
        raise ValueError(
            f"max_bucket_size must be >= 1, got {max_bucket_size}")
    rate = max(float(offered_rate_rps), 1e-9)
    fill_s = SERVE_FILL_SLACK * max_bucket_size / rate
    amortized_s = max_bucket_size * dispatch_overhead_s
    wait = max(min(fill_s, amortized_s), dispatch_overhead_s)
    if deadline_s is not None:
        wait = min(wait, deadline_s / 2.0)
    return wait


# ----------------------------------------------------------------------------
# GPU / PIMS models
# ----------------------------------------------------------------------------
def gpu_sweep(spec: StencilSpec, shape: tuple[int, ...]) -> SweepCost:
    n = math.prod(shape)
    traffic = 3.0 * n * ELEM
    t = max(GPU_LAUNCH_S, traffic / GPU_BW,
            2.0 * spec.n_taps * n / GPU_PEAK_FLOPS)
    bottleneck = "launch" if t == GPU_LAUNCH_S else "HBM"
    return SweepCost(t, float("nan"), bottleneck, {})


def pims_sweep(spec: StencilSpec, shape: tuple[int, ...]) -> SweepCost:
    n = math.prod(shape)
    atomics = spec.n_taps * n            # one atomic MAC-equivalent per tap
    t_atomic = atomics / PIMS_ATOMICS_PER_S
    t_bw = 3.0 * n * ELEM / PIMS_INTERNAL_BW
    t = max(t_atomic, t_bw)
    return SweepCost(t, float("nan"),
                     "atomics" if t == t_atomic else "internal_bw", {})


# ----------------------------------------------------------------------------
# Figure-level summaries
# ----------------------------------------------------------------------------
def speedup_table() -> dict[str, dict[str, float]]:
    """Fig. 10: Casper speedup over the CPU baseline, per stencil x level."""
    out: dict[str, dict[str, float]] = {}
    for name, spec in PAPER_STENCILS.items():
        out[name] = {}
        for level in ("L2", "L3", "DRAM"):
            shape = DOMAIN_SIZES[level][spec.ndim]
            out[name][level] = (cpu_sweep(spec, shape).seconds
                                / casper_sweep(spec, shape).seconds)
    return out


def energy_table() -> dict[str, dict[str, float]]:
    """Fig. 11: Casper energy normalized to the CPU baseline."""
    out: dict[str, dict[str, float]] = {}
    for name, spec in PAPER_STENCILS.items():
        out[name] = {}
        for level in ("L2", "L3", "DRAM"):
            shape = DOMAIN_SIZES[level][spec.ndim]
            out[name][level] = (casper_sweep(spec, shape).energy_j
                                / cpu_sweep(spec, shape).energy_j)
    return out


# Paper's reported results for validation (Table 5 cycles -> speedups).
PAPER_TABLE5_CYCLES = {
    # stencil: {level: (cpu, gpu, casper)}
    "jacobi1d": {"L2": (13358, 4030, 4569), "L3": (95251, 36134, 33220),
                 "DRAM": (3838447, 135360, 4370993)},
    "7pt1d": {"L2": (14702, 4108, 8449), "L3": (125138, 36594, 66393),
              "DRAM": (5715526, 139320, 4514872)},
    "jacobi2d": {"L2": (26457, 4646, 7658), "L3": (178032, 37248, 58734),
                 "DRAM": (8720011, 140160, 3931701)},
    "blur2d": {"L2": (95428, 6950, 55764), "L3": (742734, 41318, 446300),
               "DRAM": (22729495, 153480, 5454431)},
    "heat3d": {"L2": (39029, 5184, 29572), "L3": (296436, 36633, 286675),
               "DRAM": (7986968, 140856, 6784185)},
    "star33_3d": {"L2": (115884, 6758, 100243), "L3": (1009021, 52491,
                                                       1385955),
                  "DRAM": (9060219, 278784, 13420984)},
}

PAPER_TABLE6_ENERGY = {
    "jacobi1d": {"L2": (0.00012, 0.000468), "L3": (0.00113, 0.00341),
                 "DRAM": (0.2631221, 0.3114322)},
    "7pt1d": {"L2": (0.000144, 0.000629), "L3": (0.00145, 0.00469),
              "DRAM": (0.28253, 0.59888)},
    "jacobi2d": {"L2": (0.000256, 0.00073), "L3": (0.002, 0.0055),
                 "DRAM": (0.3483945, 0.8809648)},
    "blur2d": {"L2": (0.0009, 0.0015), "L3": (0.0075, 0.0118),
               "DRAM": (0.64639877, 1.19655244)},
    "heat3d": {"L2": (0.000386, 0.001737), "L3": (0.003364, 0.014002),
               "DRAM": (0.469465, 1.4752518)},
    "star33_3d": {"L2": (0.0011542, 0.0028739), "L3": (0.010266, 0.027749),
                  "DRAM": (0.4424779, 1.8090142)},
}

# NOTE: Table 5/6 cycle & energy counts are for the benchmark's full run
# (multiple sweeps + setup); we validate on *ratios* (speedup, normalized
# energy), which cancel the sweep count.


def paper_speedup(stencil: str, level: str) -> float:
    cpu, _, casper = PAPER_TABLE5_CYCLES[stencil][level]
    return cpu / casper


def paper_energy_ratio(stencil: str, level: str) -> float:
    cpu, casper = PAPER_TABLE6_ENERGY[stencil][level]
    return casper / cpu

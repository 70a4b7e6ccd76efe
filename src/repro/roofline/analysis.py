"""Three-term roofline from a compiled dry-run artifact (TPU v5e targets).

    compute term    = HLO_FLOPs_per_device / peak_FLOP/s
    memory term     = HLO_bytes_per_device / HBM_bw
    collective term = collective_wire_bytes_per_device / (links x link_bw)

cost_analysis() of a partitioned module reports *per-device* flops/bytes, so
no division by chip count is applied.  MODEL_FLOPS (6ND) is divided by chips
to compare against the per-device HLO flops.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any

from repro.models.config import ModelConfig

# TPU v5e hardware constants (per chip)
PEAK_FLOPS_BF16 = 197e12
HBM_BW = 819e9
ICI_LINK_BW = 50e9          # ~50 GB/s per link
ICI_LINKS = 4               # torus links usable per chip (2D torus on v5e)
HBM_BYTES = 16 * (1 << 30)


@dataclasses.dataclass
class Roofline:
    arch: str
    cell: str
    mesh: str
    n_devices: int
    flops_per_device: float
    bytes_per_device: float
    collective_bytes: float          # per-device wire bytes
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    model_flops: float               # 6ND (or 6 N_active D), whole step
    useful_flops_ratio: float        # model_flops/chips / hlo_flops
    memory_per_device: dict
    collective_ops: dict

    @property
    def step_time_lower_bound(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def roofline_fraction(self) -> float:
        """Fraction of the step spent on the compute roofline if perfectly
        overlapped = compute / max(all terms)."""
        lb = self.step_time_lower_bound
        return self.t_compute / lb if lb > 0 else 0.0

    def summary(self) -> dict:
        return {
            "arch": self.arch, "cell": self.cell, "mesh": self.mesh,
            "devices": self.n_devices,
            "flops_per_device": self.flops_per_device,
            "bytes_per_device": self.bytes_per_device,
            "collective_bytes_per_device": self.collective_bytes,
            "t_compute_s": self.t_compute, "t_memory_s": self.t_memory,
            "t_collective_s": self.t_collective,
            "bottleneck": self.bottleneck,
            "model_flops": self.model_flops,
            "useful_flops_ratio": self.useful_flops_ratio,
            "roofline_fraction": self.roofline_fraction,
            "memory": self.memory_per_device,
            "collective_ops": self.collective_ops,
        }


def n_params(cfg: ModelConfig) -> float:
    """Total and active parameter counts (rough closed form)."""
    from repro.models import make_arch
    from repro.models.common import param_count
    arch = make_arch(cfg)
    return float(param_count(arch.param_specs(cfg)))


def n_active_params(cfg: ModelConfig) -> float:
    total = n_params(cfg)
    if cfg.moe is None:
        return total
    m = cfg.moe
    expert = 3 * cfg.d_model * m.d_expert       # gate+up+down per expert
    inactive = cfg.n_layers * (m.n_experts - m.top_k) * expert
    return total - inactive


def model_flops(cfg: ModelConfig, cell: Any) -> float:
    """6 * N_active * D for training; 2 * N_active * D for inference."""
    n = n_active_params(cfg)
    tokens = cell.global_batch * (cell.seq_len if cell.kind != "decode"
                                  else 1)
    mult = 6.0 if cell.kind == "train" else 2.0
    return mult * n * tokens


def build_roofline(arch_id: str, cell, mesh_name: str, n_devices: int,
                   totals, memory: dict, cfg: ModelConfig) -> Roofline:
    """``totals``: trip-count-corrected hlo_walk.Totals (per device)."""
    flops = float(totals.flops)
    byts = float(totals.bytes)
    wire = float(totals.collective_wire_bytes)
    t_comp = flops / PEAK_FLOPS_BF16
    t_mem = byts / HBM_BW
    t_coll = wire / (ICI_LINKS * ICI_LINK_BW)
    terms = {"compute": t_comp, "memory": t_mem, "collective": t_coll}
    bottleneck = max(terms, key=terms.get)
    mf = model_flops(cfg, cell)
    ratio = (mf / n_devices) / flops if flops else 0.0
    return Roofline(
        arch=arch_id, cell=cell.name, mesh=mesh_name, n_devices=n_devices,
        flops_per_device=flops, bytes_per_device=byts,
        collective_bytes=wire, t_compute=t_comp, t_memory=t_mem,
        t_collective=t_coll, bottleneck=bottleneck, model_flops=mf,
        useful_flops_ratio=ratio, memory_per_device=memory,
        collective_ops=totals.collective_ops())

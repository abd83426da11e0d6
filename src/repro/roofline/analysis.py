"""Three-term roofline from the compiled dry-run artifact.

  compute    = dot_FLOPs_per_device    / flops_bf16
  memory     = op_bytes_per_device     / hbm_bw
  collective = wire_bytes_per_device   / ici_link_bw

with the peaks of the chip ``device_kind`` names (``roofline.hw``).

All three numerators come from the loop-aware HLO analyzer
(roofline/hlo_stats.py): XLA's ``cost_analysis()`` counts while-loop bodies
once (verified), so scan-over-layers models need explicit trip-count
multiplication. Semantics:

  * dot_FLOPs — MXU matmul flops only (elementwise excluded): the right
    numerator against the MXU peak.
  * op_bytes — fusion-granularity operand+result bytes (fusion internals
    free), the TPU fusion cost model applied to the CPU-partitioned HLO.
  * wire bytes — bandwidth-optimal-ring model per collective kind:
      all-gather   (D-1)/D × full buffer     reduce-scatter (D-1)/D × full
      all-reduce 2·(D-1)/D × buffer          all-to-all     (D-1)/D × buffer
      collective-permute 1 × buffer
    (D = replica-group size parsed per op.)

``cost_analysis`` numbers are retained in the report for reference.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from repro.roofline import hw
from repro.roofline.hlo_stats import HloStats, analyze

__all__ = ["roofline", "RooflineReport", "model_flops", "analyze", "flash_kernel_flops"]


def flash_kernel_flops(cfg, shape) -> float:
    """Analytic per-device dot FLOPs executed INSIDE the flash-attention
    kernel (perf iteration D): Pallas-internal dots under a dynamic
    (causality-skipping) loop bound are not visible to the HLO trip-count
    parser. Causal: 2 × (qk + pv) × 0.5 = 2·B·S²·h·hd per attention layer.
    """
    if getattr(cfg, "attn_impl", "blocked") != "flash" or not cfg.n_heads:
        return 0.0
    if shape.kind == "train":
        passes = 3.0  # fwd + bwd(2x) — not used: flash is fwd-only today
    else:
        passes = 1.0
    n_attn = cfg.n_layers if cfg.family != "hybrid" else cfg.n_layers // max(cfg.share_period, 1)
    b, s = shape.global_batch, shape.seq_len
    return passes * 2.0 * b * s * s * cfg.n_heads * cfg.head_dim * n_attn


@dataclasses.dataclass
class RooflineReport:
    arch: str
    shape: str
    device_kind: str
    n_devices: int
    flops_per_device: float  # loop-aware dot flops
    bytes_per_device: float  # loop-aware fusion-level bytes
    wire_bytes_per_device: float
    t_compute: float
    t_memory: float
    t_collective: float
    bottleneck: str
    model_flops: float
    useful_ratio: float  # MODEL_FLOPS / (flops_per_device * n_devices)
    collectives: dict
    xla_cost_flops: Optional[float] = None  # raw cost_analysis (loop-unaware)
    xla_cost_bytes: Optional[float] = None
    peak_memory_per_device: Optional[float] = None

    def to_dict(self):
        return dataclasses.asdict(self)

    @property
    def roofline_time(self) -> float:
        return max(self.t_compute, self.t_memory, self.t_collective)

    @property
    def roofline_fraction(self) -> float:
        """useful-FLOPs time / binding-roofline time: the fraction of the
        roofline-limited step that does model math."""
        peak = hw.peaks(self.device_kind).flops_bf16
        t_useful = (self.model_flops / self.n_devices) / peak
        return t_useful / self.roofline_time if self.roofline_time > 0 else 0.0


def model_flops(cfg, shape) -> float:
    """Reference useful FLOPs per step: 6·N_active·tokens (train),
    2·N_active·tokens (prefill/decode)."""
    n = cfg.n_active_params()
    if shape.kind == "train":
        return 6.0 * n * shape.global_batch * shape.seq_len
    if shape.kind == "prefill":
        return 2.0 * n * shape.global_batch * shape.seq_len
    return 2.0 * n * shape.global_batch  # decode: 1 token per sequence


def roofline(
    arch: str,
    shape,
    cfg,
    cost: dict,
    hlo_text: str,
    n_devices: int,
    memory_stats: Optional[dict] = None,
    *,
    device_kind: str,
) -> RooflineReport:
    pk = hw.peaks(device_kind)
    st: HloStats = analyze(hlo_text, n_devices)
    flops = st.dot_flops + flash_kernel_flops(cfg, shape) / n_devices
    nbytes = st.op_bytes
    wire = st.collective_total

    t_c = flops / pk.flops_bf16
    t_m = nbytes / pk.hbm_bw
    t_x = wire / pk.ici_link_bw
    terms = {"compute": t_c, "memory": t_m, "collective": t_x}
    bottleneck = max(terms, key=terms.get)

    mf = model_flops(cfg, shape)
    useful = mf / (flops * n_devices) if flops > 0 else 0.0

    return RooflineReport(
        arch=arch,
        shape=shape.name,
        device_kind=device_kind,
        n_devices=n_devices,
        flops_per_device=flops,
        bytes_per_device=nbytes,
        wire_bytes_per_device=wire,
        t_compute=t_c,
        t_memory=t_m,
        t_collective=t_x,
        bottleneck=bottleneck,
        model_flops=mf,
        useful_ratio=useful,
        collectives=st.collectives,
        xla_cost_flops=float(cost.get("flops", 0.0)) if cost else None,
        xla_cost_bytes=float(cost.get("bytes accessed", 0.0)) if cost else None,
        peak_memory_per_device=(memory_stats or {}).get("bytes"),
    )

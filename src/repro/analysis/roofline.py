"""Device peaks, and the three-term roofline model for TPU v5e from the
dry-run's compiled HLO.

  compute term    = dtype-weighted dot FLOPs / per-chip peak
  memory term     = HBM-traffic proxy / per-chip HBM bandwidth
  collective term = per-device collective wire bytes / per-chip ICI bandwidth

All inputs are per-device (the SPMD module is the per-device program); with
the spec's convention (totals / (chips x unit-rate)) the chip count cancels.
The dominant term is the projected step-time lower bound; the compute
fraction = compute_term / max(all terms) is the MFU-style score (§Perf).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Dict

from repro.analysis.hlo import HloSummary


@dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks of one accelerator kind."""
    bf16_flops: float           # FLOP/s
    int8_ops: float             # OP/s
    hbm_bw: float               # B/s
    ici_link_bw: float          # B/s per chip-to-chip link
    source: str


# The one peak table, keyed by `jax.Device.device_kind`.  A device that is
# not listed has no peaks: utilizations against it are "not measured".
PEAKS: Dict[str, DevicePeaks] = {
    "TPU v5 lite": DevicePeaks(
        bf16_flops=197e12, int8_ops=393e12, hbm_bw=819e9,
        ici_link_bw=1600e9 / 8 / 4,       # 1,600 Gbit/s over 4 links
        source="Google Cloud documentation, 'TPU v5e' (per chip)"),
}


# The dry-run projects onto a TPU v5e pod whatever backend compiles it
V5E = PEAKS["TPU v5 lite"]
PEAK_BF16 = V5E.bf16_flops  # FLOP/s per chip
PEAK_FP8 = V5E.int8_ops     # 8-bit MXU rate (v5e has int8, not fp8, units)
PEAK_FP32 = PEAK_BF16 / 2   # fp32 via MXU passes
HBM_BW = V5E.hbm_bw         # B/s per chip
ICI_BW = V5E.ici_link_bw    # B/s per link (1-link conservative convention)

_PEAK_BY_DTYPE = {
    "bf16": PEAK_BF16, "f16": PEAK_BF16, "f32": PEAK_FP32, "f64": PEAK_FP32 / 4,
    "f8e4m3fn": PEAK_FP8, "f8e5m2": PEAK_FP8, "f8e4m3": PEAK_FP8,
}


@dataclass
class Roofline:
    compute_s: float
    memory_s: float
    collective_s: float
    flops: float                    # per device
    mem_bytes: float                # per device
    coll_bytes: float               # per device
    flops_by_dtype: Dict[str, float]
    flops_by_tag: Dict[str, float]
    collective_bytes: Dict[str, float]
    # HBM traffic the fused kernels keep in VMEM (per device): what the
    # norm/residual/activation intermediates and kernel-interior tiles
    # would have cost under the naive op-by-op lowering
    mem_bytes_elided: float = 0.0

    @property
    def bound(self) -> str:
        terms = {"compute": self.compute_s, "memory": self.memory_s,
                 "collective": self.collective_s}
        return max(terms, key=terms.get)

    @property
    def step_time_s(self) -> float:
        """Lower bound assuming perfect overlap of the three engines."""
        return max(self.compute_s, self.memory_s, self.collective_s)

    @property
    def compute_fraction(self) -> float:
        """Fraction of the step the MXU is the binding constraint (the
        roofline score: 1.0 = perfectly compute-bound)."""
        t = self.step_time_s
        return self.compute_s / t if t > 0 else 0.0

    def as_dict(self) -> dict:
        return {
            "compute_s": self.compute_s, "memory_s": self.memory_s,
            "collective_s": self.collective_s, "bound": self.bound,
            "step_time_s": self.step_time_s,
            "compute_fraction": self.compute_fraction,
            "flops_per_device": self.flops,
            "mem_bytes_per_device": self.mem_bytes,
            "mem_bytes_elided_per_device": self.mem_bytes_elided,
            "coll_bytes_per_device": self.coll_bytes,
            "flops_by_dtype": self.flops_by_dtype,
            "flops_by_tag": self.flops_by_tag,
            "collective_bytes": self.collective_bytes,
        }


def roofline_from_summary(s: HloSummary) -> Roofline:
    compute = sum(v / _PEAK_BY_DTYPE.get(dt, PEAK_BF16)
                  for dt, v in s.flops_by_dtype.items())
    memory = s.mem_bytes / HBM_BW
    collective = s.total_collective_bytes / ICI_BW
    return Roofline(
        compute_s=compute, memory_s=memory, collective_s=collective,
        flops=s.total_flops, mem_bytes=s.mem_bytes,
        coll_bytes=s.total_collective_bytes,
        flops_by_dtype=dict(s.flops_by_dtype),
        flops_by_tag=dict(s.flops_by_tag),
        collective_bytes=dict(s.collective_bytes),
        mem_bytes_elided=s.elided_bytes)


def decoder_flops_per_token(cfg) -> float:
    """Analytic forward FLOPs per token position: 2 * N_active, embedding
    tables excluded — the per-token factor `model_flops` scales by token
    count."""
    n = cfg.n_active_params()
    n -= cfg.padded_vocab * cfg.d_model * (1 if cfg.tie_embeddings else 2)
    return 2.0 * n


def model_flops(cfg, shape) -> float:
    """Analytic 'useful' FLOPs for the whole step (all devices).

    train: 6*N_active*D tokens; prefill: 2*N_active*D; decode: 2*N_active*B
    (one token per sequence).  N excludes embedding tables."""
    n = decoder_flops_per_token(cfg) / 2.0
    if shape.kind == "train":
        return 6.0 * n * shape.seq_len * shape.global_batch
    if shape.kind == "prefill":
        return 2.0 * n * shape.seq_len * shape.global_batch
    return 2.0 * n * shape.global_batch

"""Cluster hardware description consumed by the profiler/cost model.

The TPU v5e pod is the build target (constants from the assignment); GPU-like
presets exist so the Fig.-3 reproduction benchmark can show Galvatron picking
*different* strategies on different clusters — the paper's core claim.
"""
from __future__ import annotations

import dataclasses


#: Published bf16 peak FLOP/s of one chip, keyed by ``jax.Device.device_kind``
#: as JAX reports it.  Source: Google Cloud documentation, "TPU v5e"
#: (197 TFLOP/s bf16).  A kind missing here has no MFU — never another
#: chip's peak.
PEAK_FLOPS_BY_DEVICE_KIND = {"TPU v5 lite": 197e12}


def device_peak_flops(device) -> float:
    """Peak bf16 FLOP/s of ``device``'s chip; 0.0 (no MFU) when unknown."""
    return PEAK_FLOPS_BY_DEVICE_KIND.get(device.device_kind, 0.0)


@dataclasses.dataclass(frozen=True)
class ClusterSpec:
    name: str
    chips: int
    peak_flops: float              # per chip, bf16/fp16 FLOP/s
    hbm_bytes: float               # per chip
    hbm_bw: float                  # per chip, bytes/s
    intra_bw: float                # fast-domain link bw per chip (ICI / NVLink)
    inter_bw: float                # slow-domain bw per chip (DCN / IB / eth)
    intra_size: int                # chips per fast domain (pod / node)
    intra_latency: float = 1e-6    # alpha terms (s)
    inter_latency: float = 10e-6
    flops_efficiency: float = 0.6  # attainable fraction of peak on matmuls
    mem_overhead: float = 1.15     # allocator fragmentation / workspace factor

    def link_bw(self, group_size: int) -> float:
        """Effective per-chip collective bandwidth for a group of this size."""
        return self.intra_bw if group_size <= self.intra_size else self.inter_bw

    def latency(self, group_size: int) -> float:
        return self.intra_latency if group_size <= self.intra_size else self.inter_latency


TPU_V5E_POD = ClusterSpec(
    name="tpu-v5e-256",
    chips=256,
    peak_flops=PEAK_FLOPS_BY_DEVICE_KIND["TPU v5 lite"],
    hbm_bytes=16e9,
    hbm_bw=819e9,
    intra_bw=50e9,                 # ~50 GB/s/link ICI (assignment constant)
    inter_bw=6.25e9,               # DCN-class inter-pod
    intra_size=256,
)

TPU_V5E_2POD = dataclasses.replace(TPU_V5E_POD, name="tpu-v5e-512", chips=512)

# --- GPU presets for the paper-reproduction benchmark (Fig. 3 clusters) ----
A100_NODE8 = ClusterSpec(
    name="a100-16", chips=16, peak_flops=312e12, hbm_bytes=80e9, hbm_bw=2039e9,
    intra_bw=300e9, inter_bw=25e9, intra_size=8)
H100_NODE8 = ClusterSpec(
    name="h100-16", chips=16, peak_flops=989e12, hbm_bytes=80e9, hbm_bw=3350e9,
    intra_bw=450e9, inter_bw=50e9, intra_size=8)
RTX4090_NODE8 = ClusterSpec(
    name="4090-16", chips=16, peak_flops=165e12, hbm_bytes=24e9, hbm_bw=1008e9,
    intra_bw=32e9, inter_bw=1.25e9, intra_size=8)

CLUSTERS = {c.name: c for c in (TPU_V5E_POD, TPU_V5E_2POD, A100_NODE8, H100_NODE8, RTX4090_NODE8)}

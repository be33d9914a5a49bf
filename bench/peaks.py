"""Published peaks of each accelerator the benchmark may run on, keyed by
``jax.Device.device_kind``.  A kind that is not here is an error: a share
of a peak is never taken against a default.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class Peak:
    flops_per_s: float        # dense bf16 matrix FLOP/s
    hbm_bytes_per_s: float    # HBM bandwidth
    hbm_bytes: float          # HBM capacity
    source: str


PEAKS = {
    "TPU v5 lite": Peak(
        flops_per_s=197e12, hbm_bytes_per_s=819e9, hbm_bytes=16e9,
        source="Google Cloud documentation, 'TPU v5e': 197 TFLOP/s bf16, "
               "16 GB HBM at 819 GB/s per chip"),
}


def peak_for(device_kind: str) -> Peak:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peak for device_kind {device_kind!r}; add its "
            f"row to bench/peaks.py (known: {sorted(PEAKS)})") from None


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: Peak) -> tuple[float, str]:
    """(percent of the roofline, the bound that sets it) for work of
    ``flops`` operations and ``nbytes`` bytes that took ``seconds``."""
    t_flops = flops / peak.flops_per_s
    t_bytes = nbytes / peak.hbm_bytes_per_s
    bound = "memory" if t_bytes >= t_flops else "compute"
    return 100.0 * max(t_flops, t_bytes) / seconds, bound

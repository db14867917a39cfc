"""Published peaks of the chips this repo runs on, keyed by ``device_kind``.

Look a device up with ``hw_for(jax.devices()[0].device_kind)``. A kind that
is not in ``PEAKS`` is an error, never a default: a roofline share against
another chip's peaks is a wrong number, not an approximate one.

Source for TPU v5e: Google Cloud documentation, "TPU v5e" (system
architecture table): 197 TFLOP/s bf16, 16 GB HBM2 at 819 GB/s, 1,600 Gbit/s
of chip-to-chip interconnect (four ICI links), 128 MiB of VMEM per core.
"""

from dataclasses import dataclass


@dataclass(frozen=True)
class HwSpec:
    name: str
    peak_bf16_flops: float  # FLOP/s per chip
    hbm_bandwidth: float  # bytes/s per chip
    ici_link_bandwidth: float  # bytes/s per link, per direction
    hbm_bytes: int  # HBM capacity per chip
    vmem_bytes: int  # VMEM per core
    mxu_dim: int  # systolic array tile dim


TPU_V5E = HwSpec(
    name="tpu-v5e",
    peak_bf16_flops=197e12,
    hbm_bandwidth=819e9,
    ici_link_bandwidth=50e9,
    hbm_bytes=16 * 1024**3,
    vmem_bytes=128 * 1024**2,
    mxu_dim=128,
)

# jax.devices()[0].device_kind -> peaks. JAX reports a v5e chip as
# "TPU v5 lite"; "TPU v5e" is the marketing name some releases use.
PEAKS = {
    "TPU v5 lite": TPU_V5E,
    "TPU v5e": TPU_V5E,
}


def hw_for(device_kind: str) -> HwSpec:
    """Peaks of ``device_kind``; ValueError for a kind not in ``PEAKS``."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device kind {device_kind!r}; add them "
            "to common/hw.py PEAKS with their source") from None

"""Published peaks of the chips the benchmark runs on, keyed by ``device_kind``.

Source for TPU v5e: Google Cloud documentation, "TPU v5e" (system
architecture table): 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s. JAX
reports the chip as "TPU v5 lite". A kind that is not here is an error: a
share of another chip's peak is a wrong number, not an approximate one.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no published peaks for device kind {device_kind!r}") from None

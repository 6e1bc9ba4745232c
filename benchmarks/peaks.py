"""Published peaks, keyed by jax's ``device_kind``. A device that is not
here is an error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 16 GB HBM2 at 819 GB/s,
    # 1,600 Gbit/s chip-to-chip interconnect, 197 TFLOP/s bf16
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "ici_bytes_per_s": 1600e9 / 8,
                    "hbm_bytes": 16e9, "bf16_flops": 197e12},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device_kind {device_kind!r}; "
                       f"add it to benchmarks/peaks.py with its source")
    return PEAKS[device_kind]

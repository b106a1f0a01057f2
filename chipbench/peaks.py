"""Published peaks of one chip, keyed by `device_kind` as JAX reports it.
A device that is not in the table is an error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of HBM
    # at 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect
    "TPU v5 lite": {"bf16_flops_s": 197e12, "hbm_bytes_s": 819e9,
                    "ici_bits_s": 1600e9, "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind {device_kind!r}; "
                         f"add a row to chipbench/peaks.py with its source")
    return PEAKS[device_kind]

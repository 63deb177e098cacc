"""Published peaks of one chip, keyed by the `device_kind` JAX reports.
A device that is not here is an error, not a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 16 GB of HBM at 819 GB/s,
    # 197 TFLOP/s in bf16
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "bf16_flop_per_s": 197e12,
                    "hbm_bytes": 16e9},
}


def peaks_of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(f"benchmark: no published peaks for device kind "
                         f"{device_kind!r}; add it to harness/peaks.py")

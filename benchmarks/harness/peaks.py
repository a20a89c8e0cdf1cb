"""Published peaks of the chips the benchmark runs on, keyed by
``jax.Device.device_kind``.  The benchmark's own copy: a later PR cannot
move the yardstick.  A device that is not in the table is an error."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 393 TOP/s
    # int8, 16 GB of HBM at 819 GB/s
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"add it to benchmarks/harness/peaks.py with its source")
    return PEAKS[device_kind]

"""Published peaks, keyed by the ``device_kind`` JAX reports. A device
that is not in the table is an error, never a default."""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 16 GB of HBM2e at 819 GB/s,
    # 197 TFLOP/s bf16, 393 TOP/s int8.
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "bf16_flops": 197e12, "int8_ops": 393e12,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}
PEAKS["TPU v5e"] = PEAKS["TPU v5 lite"]


def peaks_for(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add it to perfbench/lib/peaks.py "
                       "with its source")
    return PEAKS[device_kind]

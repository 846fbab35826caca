"""Peak rates of the chips the benchmark may run on, keyed by JAX's
`device_kind`. A device that is not here is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" system architecture page
(cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB HBM2e at 819 GB/s per chip.
"""

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "ops_int8": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no peak rates recorded for device kind {device_kind!r}; add "
            f"a row to benchmark/lib/peaks.py with its source") from None

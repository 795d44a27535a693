"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports it.

The yardstick for every utilization and roofline share in the benchmark: a later PR
cannot move it. A device that is not in the table is an error, not a default.
"""

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM2e at 819 GB/s
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e system architecture"},
    "TPU v5e": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                "source": "Google Cloud documentation, TPU v5e system architecture"},
}


def peaks_for(device_kind):
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; add a row "
                       f"with its source to benchmarks/peaks.py") from None

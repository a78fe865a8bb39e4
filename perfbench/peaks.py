"""Published peaks of the chips the benchmark knows, keyed by JAX's
``device_kind``. A device that is not here is an error, never a default.

TPU v5e: Google Cloud documentation, "TPU v5e" system architecture page:
197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600 Gbit/s of
chip-to-chip interconnect per chip. (The same numbers as the repo's
``bench.py`` ``PEAK_TFLOPS`` table, which has no bandwidths.)
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9,
                    "ici_bytes_per_s": 200e9},
    "TPU v5e": {"bf16_flops": 197e12, "int8_ops": 393e12,
                "hbm_bytes": 16e9, "hbm_bytes_per_s": 819e9,
                "ici_bytes_per_s": 200e9},
}


def peaks_for(device_kind):
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}")
    return PEAKS[device_kind]

"""Published peaks of one chip, keyed by ``jax.devices()[0].device_kind``.

Copied from ``bench.py:PEAKS`` (PERF.md lists the original for removal).
Source: Google Cloud documentation, "TPU v5e": 197 TFLOP/s in bf16,
819 GB/s of HBM bandwidth, 16 GB of HBM per chip.  A device that is not
in the table is an error, never a default.
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9},
}


def peaks_for(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peak recorded for device_kind {device_kind!r}; add it to "
            f"benchmarks/lib/peaks.py with its source")
    return PEAKS[device_kind]

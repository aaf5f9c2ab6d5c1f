"""Peaks of the chips the benchmark may run on, keyed by JAX's
``device_kind``. A device that is not here is an error, never a default.

"TPU v5 lite" is one TPU v5e chip. Source: Google Cloud documentation,
"TPU v5e" system architecture page (cloud.google.com/tpu/docs/v5e): per
chip 197 TFLOP/s peak in bf16, 393 TOP/s in int8, 16 GB of HBM2e at
819 GB/s, 1,600 Gbit/s of inter-chip interconnect. The same numbers
are in the ``on-chip-measurement`` guide, section 4.
"""

PEAKS = {
    "TPU v5 lite": {
        "hbm_bytes_per_s": 819e9,
        "bf16_flops_per_s": 197e12,
        "hbm_bytes": 16e9,
    },
}


def of(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(
            f"no peaks known for device kind {device_kind!r}; add it to "
            f"benchmarks/peaks.py with its source")
    return PEAKS[device_kind]

"""Published peaks of one chip, keyed by the ``device_kind`` string JAX
reports.  A kind that is not here is an error, never a default.

"TPU v5 lite" is the TPU v5e: 197 TFLOP/s dense bf16, 819 GB/s of HBM, 16 GB
(Google Cloud documentation, "TPU v5e"; the key is what the chip reported in
PR 21's runs).
"""

PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peak(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise ValueError(
            f"no published peak for device_kind {device_kind!r}; known: {sorted(PEAKS)} "
            "- add it to perfbench/peaks.py with its source"
        )
    return PEAKS[device_kind]

"""Published peaks of one chip, keyed by ``device_kind`` as JAX reports
it. One table; a kind that is not in it is an error, never a default.

Source: Google Cloud documentation, "TPU v5e" (system architecture
page): 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per
chip. (``bench.py::_TPU_PEAK_BF16`` holds the bf16 figure only; this is
the benchmark's copy, with the int8 and bandwidth rows added.)
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add a "
            f"row with its source to benchmark/harness/peaks.py") from None

"""Published peaks of the chips the benchmark may run on, keyed by
``device_kind`` as JAX reports it.  A device that is not in the table is an
error, never a default.  (The bf16 row was ``bench.py::PEAK_BF16_FLOPS``.)"""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
    # 819 GB/s, 1,600 Gbit/s of chip-to-chip interconnect per chip.
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes_per_s": 819e9,
        "ici_bytes_per_s": 200e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peaks for device_kind {device_kind!r}: add a row "
            "to benchmark/harness/peaks.py with its source"
        ) from None

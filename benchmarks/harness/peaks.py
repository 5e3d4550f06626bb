"""Published per-chip peaks, keyed by jax's `device_kind` — the
benchmark's OWN copy, so that no later PR moves a utilization by editing
the program's table (profiler/stats/flops.py::DEVICE_PEAKS holds the same
numbers today).

Source: Google Cloud documentation, "TPU v5e" (system architecture): per
chip 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s, 1,600
Gbit/s of inter-chip interconnect. jax names that chip "TPU v5 lite".
A device that is not listed is an error, never a default.
"""
from __future__ import annotations

DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "ici_bits_per_s": 1600e9},
}


def device_peaks(device_kind: str) -> dict:
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"no published peaks for device kind {device_kind!r} (have "
            f"{sorted(DEVICE_PEAKS)}): add its row, with its source, to "
            f"benchmarks/harness/peaks.py") from None

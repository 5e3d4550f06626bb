"""FLOPs accounting support: device peaks and MFU.

The per-op analytic formulas live next to the dispatcher
(``core/dispatch.py`` FLOPS_REGISTRY — matmul/conv/attention exact,
elementwise by output size); this module supplies the denominator.

Conventions (documented in DESIGN.md):
- op/layer FLOPs are FORWARD-pass analytic counts;
- a compiled TrainStep reports 3x its forward count (fwd + ~2x bwd), the
  standard transformer training accounting;
- MFU = achieved FLOP/s / device_peak_flops(); a CPU run has no MFU.
"""
from __future__ import annotations

from ...core.flags import define_flag, flag

define_flag("device_peak_flops", 0.0,
            "peak device FLOP/s used as the MFU denominator; 0 = look "
            "the device_kind up in profiler.stats.flops.DEVICE_PEAKS")

# Published per-chip peaks, keyed by jax's ``device_kind``. A device that
# is not listed is an error where a utilization is asked for, never a
# default. Source: Google Cloud documentation, "TPU v5e" (197 TFLOP/s
# bf16, 393 TOP/s int8, 16 GB HBM at 819 GB/s, 1,600 Gbps of inter-chip
# interconnect a chip over four links: 50 GB/s a link and direction).
DEVICE_PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "ici_link_bytes_per_s": 50e9},
}


def device_peaks(device_kind: str | None = None) -> dict:
    """The DEVICE_PEAKS row of `device_kind` (default: the first jax
    device's). Raises LookupError for a device that is not in the
    table."""
    if device_kind is None:
        import jax

        device_kind = jax.devices()[0].device_kind
    try:
        return DEVICE_PEAKS[device_kind]
    except KeyError:
        raise LookupError(
            f"no published peaks for device kind {device_kind!r} "
            f"(have {sorted(DEVICE_PEAKS)}); add it to "
            f"profiler.stats.flops.DEVICE_PEAKS with its source, or set "
            f"FLAGS_device_peak_flops") from None


def device_peak_flops() -> float | None:
    """MFU denominator in FLOP/s: FLAGS_device_peak_flops where set, else
    the table's bf16 peak for this device. None on the CPU backend (a
    host run reports no MFU); an accelerator that is not in the table
    raises."""
    v = float(flag("device_peak_flops"))
    if v > 0:
        return v
    import jax

    if jax.default_backend() == "cpu":
        return None
    return device_peaks()["bf16_flops"]

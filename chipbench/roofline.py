"""Peaks of the chips this benchmark runs on, and the work an update needs.

The peak table is keyed by ``device_kind`` as JAX reports it.  A kind that
is not in the table is an error, never a default.
"""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" (system architecture page): per
# chip 197 TFLOP/s bf16, 16 GB of HBM at 819 GB/s.
PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "bf16_flops_per_s": 197e12,
                    "source": "Google Cloud TPU v5e documentation"},
}

# An fp32 Adam update reads p, mu, nu and g and writes p, mu and nu:
# 7 arrays of 4 bytes per parameter.
ADAM_FP32_BYTES_PER_PARAM = 28


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no peaks known for device_kind {device_kind!r}; "
                       f"known: {sorted(PEAKS)}") from None


def required_bytes(params_applied: int) -> int:
    """HBM bytes an fp32 Adam update of ``params_applied`` real parameters
    needs, whatever implements it: no padding, copies or snapshots."""
    return ADAM_FP32_BYTES_PER_PARAM * int(params_applied)


def roofline_pct(nbytes: float, seconds: float, device_kind: str):
    """Share (%) of the HBM roofline: the least time the chip needs to
    move ``nbytes`` over the measured ``seconds``.  None when nothing was
    measured, so a reader reports nothing rather than 0."""
    if not nbytes or not seconds or seconds <= 0:
        return None
    return 100.0 * nbytes / peak(device_kind)["hbm_bytes_per_s"] / seconds

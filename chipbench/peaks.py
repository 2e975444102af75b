"""Published peaks of one chip, keyed by JAX's ``device_kind``.

Source: Google Cloud documentation, "TPU v5e"
(https://cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s
int8, 16 GB of HBM at 819 GB/s per chip.  A device that is not in the
table is an error, never a default.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
    },
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None


def bound_seconds(ops: float, nbytes: float, device_kind: str,
                  peak: str = "int8_ops"):
    """The least time the chip could take for ``ops`` operations at the
    peak rate ``peak`` moving ``nbytes``: (seconds, which bound binds)."""
    p = peaks(device_kind)
    t_ops = ops / p[peak]
    t_bytes = nbytes / p["hbm_bytes_per_s"]
    return (t_ops, "ops") if t_ops >= t_bytes else (t_bytes, "bytes")

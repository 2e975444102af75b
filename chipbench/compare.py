"""The comparison that decides ``correct``: exact, element by element.

An output that is missing, or has another shape or type than the
reference's, counts every reference element as a mismatch.
"""

from __future__ import annotations

import numpy as np


def mismatches(got, want) -> tuple:
    """(elements that differ, elements compared), on the host."""
    want = np.asarray(want)
    if got is None:
        return want.size, want.size
    got = np.asarray(got)
    if got.shape != want.shape or got.dtype != want.dtype:
        return want.size, want.size
    return int(np.count_nonzero(got != want)), want.size


def mismatches_device(got, want) -> tuple:
    """:func:`mismatches` for device arrays, counted on the device."""
    import jax.numpy as jnp
    if got is None or got.shape != want.shape or got.dtype != want.dtype:
        return int(want.size), int(want.size)
    return int(jnp.count_nonzero(got != want)), int(want.size)


def verdict(checks: dict) -> bool:
    """Every compared number within its limit (``None``: not a limit)."""
    return all(limit is None or value <= limit
               for value, limit in checks.values())

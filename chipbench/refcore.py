"""Plain bit-level models for the references, written from the papers.

Nothing here imports the system under test.  Values are unsigned N-bit
patterns held in uint32 lanes (``jax.numpy``), so N may be up to 32.

HALOC-AxA (arXiv:2510.20137, Section III, Fig. 2), for an N-bit adder
with an m-bit lower section whose low k bits are constant:

* bits k-1..0 of the sum are 1;
* bits m-3..k are ``a | b``;
* bit m-2 is ``a[m-2] ^ b[m-2]`` (the first half adder's sum);
* bit m-1 is ``(a[m-1] ^ b[m-1]) | (a[m-2] & b[m-2])`` (the second half
  adder's sum, OR-merged with the first half adder's carry);
* the upper N-m bits are the exact sum of the operands' upper parts
  plus the carry ``a[m-1] & b[m-1]``, modulo 2^N.

The truncated multiplier (Masadeh et al., and the Wu survey) drops
every partial-product cell whose column ``i + j`` is below ``t``.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _bit(x, i: int):
    return (x >> i) & 1


def haloc_add(a, b, n: int, m: int, k: int):
    """HALOC-AxA sum of uint32 patterns ``a`` and ``b``, modulo 2^n."""
    u = jnp.uint32
    s_m1 = (_bit(a, m - 1) ^ _bit(b, m - 1)) | (_bit(a, m - 2) & _bit(b, m - 2))
    s_m2 = _bit(a, m - 2) ^ _bit(b, m - 2)
    mid_mask = ((1 << (m - 2)) - 1) ^ ((1 << k) - 1)
    low = (s_m1 << (m - 1)) | (s_m2 << (m - 2)) | ((a | b) & u(mid_mask)) \
        | u((1 << k) - 1)
    carry = _bit(a, m - 1) & _bit(b, m - 1)
    high = (a >> m) + (b >> m) + carry
    s = (high << m) | low
    return s if n == 32 else s & u((1 << n) - 1)


def exact_add(a, b, n: int, m: int = 0, k: int = 0):
    """The exact N-bit adder, with HALOC-AxA's signature (the control)."""
    s = a + b
    return s if n == 32 else s & jnp.uint32((1 << n) - 1)


ADDERS = {"haloc_axa": haloc_add, "accurate": exact_add}


def to_pattern(v, n: int):
    """Signed int32 values -> their n-bit two's-complement patterns."""
    p = jax.lax.bitcast_convert_type(v.astype(jnp.int32), jnp.uint32)
    return p if n == 32 else p & jnp.uint32((1 << n) - 1)


def to_signed(p, n: int):
    """n-bit patterns -> sign-extended int32 values."""
    if n == 32:
        return jax.lax.bitcast_convert_type(p, jnp.int32)
    half = 1 << (n - 1)
    return ((p ^ jnp.uint32(half)).astype(jnp.int32) - half)


def round_shift(v, s: int):
    """Exact rounding right shift (half up) of signed int32 values."""
    return (v + (1 << (s - 1))) >> s if s else v


def truncated_planes(a_mag, t: int, n: int):
    """The multiplicand rows of a truncated n x n array multiplier.

    Row ``i`` (multiplier bit i) keeps the multiplicand's bits from
    column ``max(t - i, 0)`` up, so ``trunc(a, b) = sum_i 2^i * b_i *
    rows[i]``.  ``a_mag`` is the unsigned multiplicand magnitude."""
    rows = []
    for i in range(n):
        keep = max(t - i, 0)
        rows.append((a_mag >> keep) << keep)
    return rows

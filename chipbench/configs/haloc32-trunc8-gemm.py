"""Plain reference of ``haloc32-trunc8-gemm``: an int8 GEMM whose
products run a truncated array multiplier and whose K tiles are folded
by the HALOC-AxA N=32 adder.

A product is ``sign(a) sign(b) trunc(|a|, |b|)``.  ``trunc`` keeps the
cells of the n x n array multiplier whose column is at least ``t``, so
with ``rows[i]`` the multiplicand with its bits below ``t - i`` cleared,
``trunc(|a|, |b|) = sum_i 2^i b_i rows[i]``.  Summed over one K tile
that is ``sum_i 2^i (sign(a) rows_i(|a|)) @ (sign(b) b_i)``: eight
exact int8 matrix products with int32 sums.  The tiles' sums, taken in
K order, fold through the adder as 32-bit patterns.  K is padded with
zeros to whole tiles; a zero operand adds nothing inside its tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import refcore


def _gemm(a, b, *, tile, n_bits, trunc_bits, adder):
    m, k = a.shape
    n = b.shape[1]
    tiles = -(-k // tile)
    pad = tiles * tile - k
    a = jnp.pad(a.astype(jnp.int32), ((0, 0), (0, pad)))
    b = jnp.pad(b.astype(jnp.int32), ((0, pad), (0, 0)))
    a_sign, a_mag = jnp.sign(a), jnp.abs(a)
    b_sign, b_mag = jnp.sign(b), jnp.abs(b)
    rows = refcore.truncated_planes(a_mag, trunc_bits, n_bits)
    part = jnp.zeros((tiles, m, n), jnp.int32)
    for i, row in enumerate(rows):
        lhs = (a_sign * row).astype(jnp.int8).reshape(m, tiles, tile)
        rhs = (b_sign * ((b_mag >> i) & 1)).astype(jnp.int8) \
            .reshape(tiles, tile, n)
        dot = jnp.einsum("mtk,tkn->tmn", lhs, rhs,
                         preferred_element_type=jnp.int32)
        part = part + (dot << i)
    add = refcore.ADDERS[adder["kind"]]
    nb = adder["n_bits"]
    acc = refcore.to_pattern(part[0], nb)
    for t in range(1, tiles):
        acc = add(acc, refcore.to_pattern(part[t], nb), nb,
                  adder.get("lsm_bits", 0), adder.get("const_bits", 0))
    return refcore.to_signed(acc, nb)


@functools.lru_cache(maxsize=None)
def _compiled(tile, n_bits, trunc_bits, adder_items):
    return jax.jit(functools.partial(_gemm, tile=tile, n_bits=n_bits,
                                     trunc_bits=trunc_bits,
                                     adder=dict(adder_items)))


def reference(a, b, cfg):
    """int8 (M, K) @ int8 (K, N) -> int32 (M, N), on the device."""
    mul = cfg["multiplier"]
    fn = _compiled(cfg["block"][2], mul["n_bits"], mul["trunc_bits"],
                   tuple(sorted(cfg["adder"].items())))
    return fn(jnp.asarray(a), jnp.asarray(b))

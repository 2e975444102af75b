"""Pallas TPU kernel: int8 GEMM with approximate inter-tile accumulation.

TPU-native adaptation of the paper's MAC-array deployment: a systolic MXU
computes each (bm, bk)x(bk, bn) int8 partial product EXACTLY (the MXU is
fixed silicon — there is nothing to approximate inside it), and the
paper's adder sits where an AxA ASIC would put it: on the ACCUMULATOR that
combines partial sums across K tiles.  This preserves the paper's
error/energy trade-off point (accumulator adds dominate adder count in a
MAC array) while keeping the matmul on the MXU.

Grid (M/bm, N/bn, K/bk), K innermost; the int32 output block is revisited
across the K dimension and accumulated through the approximate adder.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.adders import approx_add_mod
from repro.core.specs import AdderSpec


def _kernel(a_ref, b_ref, o_ref, *, spec: AdderSpec, fast: bool):
    partial = jnp.dot(a_ref[...], b_ref[...],
                      preferred_element_type=jnp.int32)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = partial

    @pl.when(pl.program_id(2) != 0)
    def _acc():
        acc = jax.lax.bitcast_convert_type(o_ref[...], jnp.uint32)
        par = jax.lax.bitcast_convert_type(partial, jnp.uint32)
        s = approx_add_mod(acc, par, spec, fast=fast)
        o_ref[...] = jax.lax.bitcast_convert_type(s, jnp.int32)


def approx_matmul_pallas(a, b, spec: AdderSpec, *,
                         block=(128, 128, 128), interpret: bool,
                         fast: bool = False):
    """a: int8 (M, K); b: int8 (K, N) -> int32 (M, N).

    K-tile partial products are exact (MXU); their accumulation runs
    through the approximate adder (two's complement mod 2^32);
    ``fast`` folds through the registered fused form (bit-identical)."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    bm, bn, bk = (min(block[0], m), min(block[1], n), min(block[2], k))
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        functools.partial(_kernel, spec=spec, fast=fast),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        interpret=interpret,
    )(a, b)

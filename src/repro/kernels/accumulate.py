"""Pallas TPU kernel: fused weighted K-term approximate accumulation.

A separable image-filter tap (or any K-operand reduction through the
approximate adder) is K-1 dependent adds; dispatched as K-1 elementwise
kernels that costs 2(K-1) HBM reads and K-1 writes of intermediates.
This kernel keeps the whole accumulation on one VMEM-resident tile: the
K stacked terms are read once, multiplied by their static integer
weights (exact — the hardware's tap multipliers are not approximated),
folded left through the approximate adder mod 2^N, and written once.

Tiles are (K, 256, 256) int32: at the K<=9 of a 3x3 filter that is
~2.25 MiB resident, well inside a TPU core's ~16 MiB VMEM, and both
trailing dims are multiples of the (8, 128) VREG lane layout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.adders import approx_add_mod
from repro.core.specs import AdderSpec


def scale_mod_u32(term, w: int, n_bits: int):
    """Exact ``term * w`` reduced mod 2^N on uint32 lanes (uint32
    multiply wraps at 2^32, so only N < 32 needs an explicit mask).
    Shared by the kernel body and the jax backend emulation — the two
    must stay bit-identical."""
    if w == 1:
        return term
    term = term * jnp.uint32(w & 0xFFFFFFFF)
    if n_bits < 32:
        term = term & jnp.uint32((1 << n_bits) - 1)
    return term


def _kernel(t_ref, o_ref, *, spec: AdderSpec, weights, fast: bool):
    acc = None
    for k, w in enumerate(weights):
        term = jax.lax.bitcast_convert_type(t_ref[k], jnp.uint32)
        term = scale_mod_u32(term, w, spec.n_bits)
        acc = term if acc is None else approx_add_mod(acc, term, spec,
                                                      fast=fast)
    o_ref[...] = jax.lax.bitcast_convert_type(acc, jnp.int32)


def accumulate_pallas(terms, spec: AdderSpec, *, weights=None,
                      block=(256, 256), interpret: bool,
                      fast: bool = False):
    """terms: int32 (K, M, N) two's-complement containers; returns the
    weighted approximate fold, int32 (M, N).  ``weights`` are K static
    Python ints (default all-ones); ``fast`` folds through the
    registered fused adder form (bit-identical)."""
    if terms.ndim != 3:
        raise ValueError(f"stack the terms on axis 0: expected (K, M, N), "
                         f"got shape {terms.shape}")
    k, m, n = terms.shape
    ws = tuple(weights) if weights is not None else (1,) * k
    if len(ws) != k:
        # same contract as backends._norm_weights (and survives -O)
        raise ValueError(f"{len(ws)} weights for {k} stacked terms")
    bm, bn = min(block[0], m), min(block[1], n)
    if m % bm or n % bn:
        raise ValueError(f"({m}, {n}) is not a multiple of the "
                         f"({bm}, {bn}) block; pad first (backends.py)")
    grid = (m // bm, n // bn)
    return pl.pallas_call(
        functools.partial(_kernel, spec=spec, weights=ws, fast=fast),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        grid=grid,
        in_specs=[pl.BlockSpec((k, bm, bn), lambda i, j: (0, i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        interpret=interpret,
        name="accumulate",
    )(terms)

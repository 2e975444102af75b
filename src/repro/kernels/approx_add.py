"""Pallas TPU kernel: fused elementwise approximate add (HALOC-AxA family).

The bit-exact adder emulation is ~15 elementwise bitwise ops; unfused that
is ~15 HBM round-trips of intermediates.  This kernel performs the whole
pipeline on VMEM-resident (block_m, block_n) int32 tiles: one read of each
operand, one write of the sum — the arithmetic-intensity floor for an
elementwise op.

Tiles are (256, 256) int32 by default: 256 KiB per operand block, 3 blocks
resident = 768 KiB, well inside a TPU core's ~16 MiB VMEM, and both dims
are multiples of the (8, 128) VREG lane layout.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.adders import approx_add_mod
from repro.core.specs import AdderSpec


def _kernel(a_ref, b_ref, o_ref, *, spec: AdderSpec, fast: bool):
    a = a_ref[...]
    b = b_ref[...]
    au = jax.lax.bitcast_convert_type(a, jnp.uint32)
    bu = jax.lax.bitcast_convert_type(b, jnp.uint32)
    s = approx_add_mod(au, bu, spec, fast=fast)
    o_ref[...] = jax.lax.bitcast_convert_type(s, jnp.int32)


def approx_add_pallas(a, b, spec: AdderSpec, *, block=(256, 256),
                      interpret: bool, fast: bool = False):
    """a, b: int32 (M, N) two's-complement fixed point; returns int32.

    ``fast`` selects the registered algebraically-fused adder form for
    the in-kernel fold (bit-identical to the reference form)."""
    assert a.shape == b.shape and a.ndim == 2
    m, n = a.shape
    bm, bn = min(block[0], m), min(block[1], n)
    assert m % bm == 0 and n % bn == 0, "pad to block multiples (see ops.py)"
    grid = (m // bm, n // bn)
    return pl.pallas_call(
        functools.partial(_kernel, spec=spec, fast=fast),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
            pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        interpret=interpret,
        name="approx_add",
    )(a, b)

"""Pallas TPU kernel: radix-2 FFT butterfly stage on fixed point.

One stage of the paper's image FFT: t = W * b (exact Q-format multiplies,
"accurate multipliers"), then top = a + t, bot = a - t through the
approximate adder (sub = exact two's-complement negate + approximate add).
Inverse stages additionally halve with round-to-nearest.

Data layout: the caller supplies the stage's paired operands as separate
(rows, half) planes (a = even group, b = odd group) plus per-column
twiddles (Q1.14); everything is elementwise across the block, so tiles
are (block_rows, half)-wide VMEM slabs.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.adders import approx_add_mod
from repro.core.specs import AdderSpec

TWIDDLE_FRAC = 14


def _to_u(x):
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


def _to_i(x):
    return jax.lax.bitcast_convert_type(x, jnp.int32)


def _approx_add_i32(a, b, spec):
    return _to_i(approx_add_mod(_to_u(a), _to_u(b), spec))


def _approx_sub_i32(a, b, spec):
    return _approx_add_i32(a, (-b), spec)  # exact negate, approx add


def _halve(x):
    return (x + 1) >> 1


def _mul_q14(x, w):
    """Exact (x * w + half) >> 14 for int32 x and Q1.14 w, WITHOUT int64
    (TPU has no 64-bit lanes): 16-bit limb decomposition.

    x = hi*2^16 + lo with hi = x >> 16 (arithmetic), lo = x & 0xffff >= 0.
    hi*w*2^16 is divisible by 2^14, so the rounded shift splits exactly:
       (x*w + half) >> 14  ==  (hi*w) << 2  +  (lo*w + half) >> 14.
    |hi*w| <= 2^29 and |lo*w| <= 2^30 both fit int32."""
    half = jnp.int32(1 << (TWIDDLE_FRAC - 1))
    hi = x >> 16
    lo = x & jnp.int32(0xFFFF)
    return (hi * w << (16 - TWIDDLE_FRAC)) + ((lo * w + half) >> TWIDDLE_FRAC)


def _kernel(ar_ref, ai_ref, br_ref, bi_ref, wr_ref, wi_ref,
            tr_ref, ti_ref, cr_ref, ci_ref, *, spec: AdderSpec,
            inverse: bool):
    ar, ai = ar_ref[...], ai_ref[...]
    br, bi = br_ref[...], bi_ref[...]
    wr, wi = wr_ref[...], wi_ref[...]
    # exact ("accurate") multiplies with round-to-nearest
    rr = _mul_q14(br, wr)
    ri = _mul_q14(br, wi)
    ir = _mul_q14(bi, wr)
    ii = _mul_q14(bi, wi)
    t_re = _approx_sub_i32(rr, ii, spec)
    t_im = _approx_add_i32(ri, ir, spec)
    top_re = _approx_add_i32(ar, t_re, spec)
    top_im = _approx_add_i32(ai, t_im, spec)
    bot_re = _approx_sub_i32(ar, t_re, spec)
    bot_im = _approx_sub_i32(ai, t_im, spec)
    if inverse:
        top_re, top_im = _halve(top_re), _halve(top_im)
        bot_re, bot_im = _halve(bot_re), _halve(bot_im)
    tr_ref[...], ti_ref[...] = top_re, top_im
    cr_ref[...], ci_ref[...] = bot_re, bot_im


def butterfly_pallas(a_re, a_im, b_re, b_im, w_re, w_im,
                     spec: AdderSpec, *, inverse: bool = False,
                     block_rows: int = 256, interpret: bool):
    """All inputs int32 (rows, half); twiddles int32 (half,) Q1.14.
    Returns (top_re, top_im, bot_re, bot_im)."""
    rows, half = a_re.shape
    br = min(block_rows, rows)
    assert rows % br == 0
    grid = (rows // br,)
    w_re2 = jnp.broadcast_to(w_re[None, :], (1, half))
    w_im2 = jnp.broadcast_to(w_im[None, :], (1, half))
    row_spec = pl.BlockSpec((br, half), lambda i: (i, 0))
    w_spec = pl.BlockSpec((1, half), lambda i: (0, 0))
    out = jax.ShapeDtypeStruct((rows, half), jnp.int32)
    return pl.pallas_call(
        functools.partial(_kernel, spec=spec, inverse=inverse),
        out_shape=(out, out, out, out),
        grid=grid,
        in_specs=[row_spec, row_spec, row_spec, row_spec, w_spec, w_spec],
        out_specs=(row_spec, row_spec, row_spec, row_spec),
        interpret=interpret,
    )(a_re, a_im, b_re, b_im, w_re2, w_im2)

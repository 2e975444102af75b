"""Pallas MAC kernels: approximate products composed with approximate
accumulation, VMEM-resident.

Three entry points, mirroring the adder-side kernel set.  A product
computed on the VPU runs the registered multiplier impl
(:func:`repro.ax.mul.impls.approx_mul`) in the kernel body: Mosaic
lowers only 2-D gathers, so no product table rides along as a VMEM
operand.

* :func:`mul_elementwise_pallas` — the elementwise approximate
  multiplier on (256, 256) int32 tiles.

* :func:`mac_matmul_pallas` — signed MAC GEMM, grid (M/bm, N/bn,
  K/bk), K innermost, output block revisited.  The products of a K
  tile sum EXACTLY into an int32 partial (int32 wraparound is
  associative mod 2^32, so in-tile order cannot matter); the
  approximate adder folds the partials across K tiles — the same
  placement as the adder-only kernel.  The partial takes one of two
  forms, chosen by the multiplier spec (:func:`plane_groups`):

  - *MXU planes*, for a pruned array multiplier (the registry's
    ``row_cut``) at ``n_bits <= 8``.  Row ``i`` of such an array keeps
    the multiplicand bits at and above a cut ``c(i)``; grouping the
    rows by cut, with ``bits(c)`` the mask of a group's rows,

        sum_k sign(a) sign(b) approx(|a|, |b|)
            = sum_c (sign(a) (|a| with its low c bits cleared))
                    @ (sign(b) (|b| & bits(c)))

    exactly.  Both factors lie in [-128, 127] (a magnitude of 128
    only at -128), so each group is one int8 MXU matmul with int32
    sums: a product is at most 2^14 and a tile's sum stays far inside
    int32.  The masks carry the 2^i row weights, so nothing is scaled
    after the dot.  Truncated n8t4 has cuts 4, 3, 2, 1, 0, 0, 0, 0:
    five matmuls a tile step.
  - *VPU columns*, for every other multiplier (Mitchell) and for
    ``n_bits > 8``, whose planes no longer fit int8: each K step
    multiplies one column of the A tile (rotated into lane 0) by one
    row of the B tile with the registered multiplier.

* :func:`conv2d_mac_pallas` — the 2D MAC convolution: per-tap
  sign-magnitude products against the static kernel weights, folded
  through the approximate adder, sign-extended, exact rounding shift.
  One program per (image, row block) with an in-kernel halo, exactly
  like the filter-chain kernel (:mod:`repro.kernels.stencil`).

All three are bit-identical to the jax/numpy MAC paths: the
sign-magnitude rule and the operand order are those of the product
tables the other backends gather from (``repro.ax.mul.lut``), and the
fold order is the same.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.ax.mul.impls import approx_mul
from repro.ax.mul.registry import get_multiplier
from repro.ax.mul.specs import MulSpec
from repro.core.adders import approx_add_mod
from repro.core.specs import AdderSpec
from repro.kernels.stencil import edge_views, plane_index, row_blocked_call


# ------------------------------------------------- elementwise mul --

def _mul_kernel(a_ref, b_ref, o_ref, *, mul_spec: MulSpec, fast: bool):
    au = jax.lax.bitcast_convert_type(a_ref[...], jnp.uint32)
    bu = jax.lax.bitcast_convert_type(b_ref[...], jnp.uint32)
    p = approx_mul(au, bu, mul_spec, fast=fast)
    o_ref[...] = jax.lax.bitcast_convert_type(p, jnp.int32)


def mul_elementwise_pallas(a, b, mul_spec: MulSpec, *, interpret: bool,
                           block=(256, 256), fast: bool = False):
    """a, b: int32 (M, N) unsigned N-bit container patterns; returns the
    full approximate product, int32 (M, N)."""
    assert a.shape == b.shape and a.ndim == 2
    m, n = a.shape
    bm, bn = min(block[0], m), min(block[1], n)
    assert m % bm == 0 and n % bn == 0, "pad to block multiples"
    tile = pl.BlockSpec((bm, bn), lambda i, j: (i, j))
    return pl.pallas_call(
        functools.partial(_mul_kernel, mul_spec=mul_spec, fast=fast),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        grid=(m // bm, n // bn),
        in_specs=[tile, tile],
        out_specs=tile,
        interpret=interpret,
    )(a, b)


# --------------------------------------------------- MAC matmul --

def _signed_operand(v, w: int):
    """The N-bit two's-complement value of int32 lanes ``v`` in
    sign-magnitude form: (magnitude as uint32, sign in {-1, 0, 1}) —
    the rule ``repro.ax.mul.lut.signed_mul_table`` is built by."""
    half = 1 << (w - 1)
    s = ((v & ((1 << w) - 1)) ^ half) - half
    mag = jax.lax.bitcast_convert_type(jnp.abs(s), jnp.uint32)
    return mag, (s > 0).astype(jnp.int32) - (s < 0).astype(jnp.int32)


@functools.lru_cache(maxsize=None)
def plane_groups(mul_spec: MulSpec):
    """The MXU form's groups ``((cut, rows), ...)``, largest cut first —
    ``rows`` the mask of the multiplier bits whose row keeps the
    multiplicand at and above ``cut`` — or ``None`` where the partial
    takes the VPU column loop (no registered ``row_cut``, or operands
    wider than int8)."""
    row_cut = get_multiplier(mul_spec.kind).row_cut
    if row_cut is None or mul_spec.n_bits > 8:
        return None
    groups = {}
    for i in range(mul_spec.n_bits):
        cut = row_cut(mul_spec, i)
        groups[cut] = groups.get(cut, 0) | (1 << i)
    return tuple(sorted(groups.items(), reverse=True))


def mac_form(mul_spec: MulSpec) -> dict:
    """The form the MAC GEMM kernel takes for ``mul_spec``, and its
    plane matmuls per K tile."""
    groups = plane_groups(mul_spec)
    if groups is None:
        return {"form": "vpu_columns", "mxu_dots": 0}
    return {"form": "mxu_planes", "mxu_dots": len(groups)}


def _plane_partial(a, b, w: int, groups):
    """One K tile's exact partial as grouped plane matmuls on the MXU."""
    amag, asgn = _signed_operand(a.astype(jnp.int32), w)
    bmag, bsgn = _signed_operand(b.astype(jnp.int32), w)
    partial = None
    for cut, rows in groups:
        lhs = asgn * ((amag >> cut) << cut).astype(jnp.int32)
        rhs = bsgn * (bmag & rows).astype(jnp.int32)
        dot = jnp.dot(lhs.astype(jnp.int8), rhs.astype(jnp.int8),
                      preferred_element_type=jnp.int32)
        partial = dot if partial is None else partial + dot
    return partial


def _column_partial(a_ref, b_ref, shape, mul_spec: MulSpec, fast: bool,
                    bk: int):
    """One K tile's exact partial, one K column a loop step on the VPU."""
    w = mul_spec.n_bits

    def step(j, carry):
        a, part = carry                    # a: (bm, bk), column j at lane 0
        amag, asgn = _signed_operand(a[:, :1], w)
        bmag, bsgn = _signed_operand(b_ref[pl.ds(j, 1), :], w)
        p = approx_mul(jnp.broadcast_to(amag, shape),
                       jnp.broadcast_to(bmag, shape), mul_spec,
                       fast=fast)
        p = jax.lax.bitcast_convert_type(p, jnp.int32) * (asgn * bsgn)
        return pltpu.roll(a, bk - 1, 1), part + p

    _, partial = jax.lax.fori_loop(
        0, bk, step, (a_ref[...], jnp.zeros(shape, jnp.int32)))
    return partial


def _mac_matmul_kernel(a_ref, b_ref, o_ref, *, spec: AdderSpec,
                       mul_spec: MulSpec, fast: bool, bk: int):
    groups = plane_groups(mul_spec)
    if groups is None:
        partial = _column_partial(a_ref, b_ref, o_ref.shape, mul_spec,
                                  fast, bk)
    else:
        partial = _plane_partial(a_ref[...], b_ref[...], mul_spec.n_bits,
                                 groups)

    @pl.when(pl.program_id(2) == 0)
    def _init():
        o_ref[...] = partial

    @pl.when(pl.program_id(2) != 0)
    def _acc():
        acc = jax.lax.bitcast_convert_type(o_ref[...], jnp.uint32)
        par = jax.lax.bitcast_convert_type(partial, jnp.uint32)
        s = approx_add_mod(acc, par, spec, fast=fast)
        o_ref[...] = jax.lax.bitcast_convert_type(s, jnp.int32)


def mac_matmul_pallas(a, b, spec: AdderSpec, mul_spec: MulSpec, *,
                      interpret: bool, block=(128, 128, 128),
                      fast: bool = False):
    """a: (M, K); b: (K, N) -> int32 (M, N), operands read as signed
    ``mul_spec.n_bits``-bit values: int8 where :func:`plane_groups`
    gives the MXU form, int32 on the VPU column loop.

    Every product is ``sign(a) * sign(b) * approx(|a|, |b|)`` (zero for
    a zero operand, so callers may zero-pad ragged K tiles without
    changing the result); in-tile accumulation is exact int32 — as
    grouped plane matmuls on the MXU for a pruned array multiplier at
    ``n_bits <= 8``, else one K column a step on the VPU — and
    inter-tile accumulation runs the approximate adder."""
    m, k = a.shape
    k2, n = b.shape
    assert k == k2
    bm, bn, bk = (min(block[0], m), min(block[1], n), min(block[2], k))
    assert m % bm == 0 and n % bn == 0 and k % bk == 0
    return pl.pallas_call(
        functools.partial(_mac_matmul_kernel, spec=spec,
                          mul_spec=mul_spec, fast=fast, bk=bk),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.int32),
        grid=(m // bm, n // bn, k // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        interpret=interpret,
        name="mac_matmul",
    )(a, b)


# ------------------------------------------------------ conv2d MAC --

def _conv2d(x, row0, *, spec: AdderSpec, mul_spec: MulSpec, kh: int,
            kw: int, weights, shift: int, fast: bool, h: int, w: int):
    rows, cols = plane_index(x.shape, row0)
    mask = jnp.uint32((1 << spec.n_bits) - 1)
    sign = jnp.uint32(1 << (spec.n_bits - 1))
    cy, cx = kh // 2, kw // 2
    taps = iter(weights)
    acc = None
    for rv in edge_views(x, 0, range(-cy, kh - cy), rows, h):
        for view in edge_views(rv, 1, range(-cx, kw - cx), cols, w):
            wt = next(taps)
            # sign(w) * approx(|v|, |w|), negated for v < 0: the
            # per-tap product column of repro.ax.mul.lut.tap_tables.
            mag = jax.lax.bitcast_convert_type(jnp.abs(view), jnp.uint32)
            p = approx_mul(mag, jnp.full(view.shape, abs(wt), jnp.uint32),
                           mul_spec, fast=fast)
            p = jax.lax.bitcast_convert_type(p, jnp.int32)
            if wt < 0:
                p = -p
            p = jnp.where(view < 0, -p, p)
            u = jax.lax.bitcast_convert_type(p, jnp.uint32) & mask
            acc = u if acc is None else approx_add_mod(acc, u, spec,
                                                       fast=fast)
    s = jax.lax.bitcast_convert_type((acc ^ sign) - sign, jnp.int32)
    if shift:
        s = (s + (1 << (shift - 1))) >> shift
    return s


@functools.partial(jax.jit,
                   static_argnames=("spec", "mul_spec", "kernel", "shift",
                                    "interpret", "fast"))
def conv2d_mac_pallas(q, spec: AdderSpec, mul_spec: MulSpec, kernel, *,
                      interpret: bool, shift: int = 0, fast: bool = False):
    """q: signed int32 (..., H, W), |q| < 2^w; ``kernel`` a static
    tuple-of-tuples of integer weights with odd dims.  One program per
    (image, row block), replicate-edge taps — the MAC twin of
    ``filter_chain_pallas``."""
    if q.ndim < 2:
        raise ValueError(f"conv2d needs (..., H, W); got {q.shape}")
    kh = len(kernel)
    kw = len(kernel[0])
    weights = tuple(int(w) for row in kernel for w in row)
    for wt in weights:
        if abs(wt) >= 1 << mul_spec.n_bits:
            raise ValueError(
                f"kernel weight {wt} exceeds the {mul_spec.n_bits}-bit "
                f"multiplier operand range (|w| < {1 << mul_spec.n_bits})")
    shape = q.shape
    h, w = shape[-2:]
    b = int(np.prod(shape[:-2])) if shape[:-2] else 1
    body = functools.partial(_conv2d, spec=spec, mul_spec=mul_spec, kh=kh,
                             kw=kw, weights=weights, shift=shift,
                             fast=fast, h=h, w=w)
    out = row_blocked_call(body, q.reshape(b, h, w).astype(jnp.int32),
                           reach=kh // 2, interpret=interpret,
                           name="conv2d_mac")
    return out.reshape(shape)

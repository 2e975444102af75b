"""Pallas kernel: multi-stage separable-filter chain on VMEM-resident rows.

A pipeline of separable filter passes (the gaussian/box blurs, the
sobel smooth+diff gradients) dispatched pass-by-pass costs one HBM
round-trip of the full image per pass: write the stage output, read it
back as the next stage's input.  This kernel keeps each block of rows
resident in VMEM across ALL stages: the block (plus its halo) is read
once, every :class:`~repro.ax.backends.FilterStage` — replicate-edge
taps, exact integer tap weights, the K-1 approximate adds, sign
extension and the exact rounding shift — runs on the resident values,
and the final stage's output is written once.

The grid runs one program per (image, row block); the halo is the sum
of the vertical stages' tap reach (:mod:`repro.kernels.stencil`).  The
per-stage math is the exact sequence the jax backend emulation
performs, so the chain is bit-identical to stage-by-stage
``accumulate_signed`` dispatches.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.adders import approx_add_mod
from repro.core.specs import AdderSpec
from repro.kernels.accumulate import scale_mod_u32
from repro.kernels.stencil import edge_views, plane_index, row_blocked_call


def _chain(x, row0, *, spec: AdderSpec, stages, fast: bool, h: int,
           w: int):
    rows, cols = plane_index(x.shape, row0)
    mask = jnp.int32((1 << spec.n_bits) - 1)
    sign = jnp.int32(1 << (spec.n_bits - 1))
    for st in stages:
        axis, index, n = (0, rows, h) if st.axis == -2 else (1, cols, w)
        acc = None
        for view, wt in zip(edge_views(x, axis, st.offsets, index, n),
                            st.weights):
            u = jax.lax.bitcast_convert_type(view & mask, jnp.uint32)
            u = scale_mod_u32(u, wt, spec.n_bits)
            acc = u if acc is None else approx_add_mod(acc, u, spec,
                                                       fast=fast)
        s = jax.lax.bitcast_convert_type(acc, jnp.int32)
        s = (s ^ sign) - sign
        if st.shift:
            s = (s + (1 << (st.shift - 1))) >> st.shift
        x = s
    return x


@functools.partial(jax.jit,
                   static_argnames=("spec", "stages", "interpret", "fast"))
def filter_chain_pallas(q, spec: AdderSpec, stages, *, interpret: bool,
                        fast: bool = False):
    """q: signed int32 (..., H, W) fixed-point containers of
    ``spec.n_bits`` significant bits; ``stages`` a static tuple of
    :class:`~repro.ax.backends.FilterStage` with axes -1/-2.  Returns
    the chained filter output, same shape, one kernel dispatch."""
    if q.ndim < 2:
        raise ValueError(f"filter_chain needs (..., H, W); got {q.shape}")
    norm = []
    for st in stages:
        ax = st.axis - q.ndim if st.axis >= 0 else st.axis
        if ax not in (-1, -2):
            raise ValueError(
                f"the fused chain kernel taps the image plane only "
                f"(axis -1/-2); got axis {st.axis}")
        if len(st.offsets) != len(st.weights):
            raise ValueError(f"{len(st.weights)} weights for "
                             f"{len(st.offsets)} taps")
        norm.append(st._replace(axis=ax))
    shape = q.shape
    h, w = shape[-2:]
    b = int(np.prod(shape[:-2])) if shape[:-2] else 1
    reach = sum(max(max(st.offsets), -min(st.offsets), 0)
                for st in norm if st.axis == -2)
    body = functools.partial(_chain, spec=spec, stages=tuple(norm),
                             fast=fast, h=h, w=w)
    out = row_blocked_call(body, q.reshape(b, h, w), reach=reach,
                           interpret=interpret, name="conv_chain")
    return out.reshape(shape)

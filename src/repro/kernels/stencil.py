"""Replicate-edge stencils for the Pallas image kernels: shifted views
built from rotations, and a row-blocked grid with an in-kernel halo.

Mosaic has no lowering for an edge-mode pad, so the kernels never pad.
A view shifted by ``o`` along an axis with the edge replicated is
``|o|`` unit steps, each one ``pltpu.roll`` plus a select that keeps
the element where the rotation wrapped around the image edge::

    step(x)[i] = x[i]        at the image's last index (first, for -1)
                 x[i + 1]    elsewhere                 (x[i - 1])

so ``|o|`` steps give ``x[clip(i + o, 0, n - 1)]`` — the view
:func:`repro.ax.backends.edge_taps` builds with a pad, which stays the
oracle the cross-backend tests hold these kernels to.  The select
compares ABSOLUTE image indices, so the same code is right on a whole
plane and on a window of rows.

A megapixel plane does not fit VMEM whole (1024x1024 int32 is 4 MiB,
double-buffered in and out, plus the stencil's temporaries), so
:func:`row_blocked_call` splits the rows into blocks and hands each
program its block plus ``halo`` rows above and below, read as two more
small blocks of the same array.  Rotation garbage enters a window only
within the stencil's reach of a window edge, and ``halo >= reach``
keeps it out of the block's own rows.  At the image's top and bottom
the halo blocks are clamped copies of real rows; no row inside the
image ever reads them, because the select replicates the true edge row
first.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

#: Elements of one row block: 128K int32 is 512 KiB, so the block, its
#: halo, the double buffers and a 3x3 stencil's temporaries stay well
#: inside a TPU core's default scoped VMEM.
BLOCK_ELEMS = 128 * 1024

_SUBLANES = 8
_LANES = 128


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def edge_views(x, axis: int, offsets, index, n: int):
    """Replicate-edge shifted views of ``x`` along ``axis``, one per
    offset: ``view[i] = x[j]`` where ``index[j] = clip(index[i] + o, 0,
    n - 1)``.  ``index`` holds each element's absolute image position
    along ``axis`` (same shape as ``x``) and ``n`` the image extent."""
    size = x.shape[axis]
    views = {0: x}
    for sign, edge, amount in ((1, n - 1, size - 1), (-1, 0, 1)):
        v = x
        for step in range(1, max([sign * o for o in offsets] + [0]) + 1):
            v = jnp.where(index == edge, v, pltpu.roll(v, amount, axis))
            views[sign * step] = v
    return [views[o] for o in offsets]


def plane_index(shape, row0):
    """Absolute (row, col) image positions of a ``shape`` window whose
    first row is image row ``row0``."""
    rows = jax.lax.broadcasted_iota(jnp.int32, shape, 0) + row0
    cols = jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return rows, cols


def _kernel(*refs, body, bh: int, halo: int):
    o_ref = refs[-1]
    row0 = pl.program_id(1) * bh - halo
    if halo:
        x = jnp.concatenate([refs[1][...], refs[0][...], refs[2][...]],
                            axis=0)
        o_ref[...] = body(x, row0)[halo:halo + bh]
    else:
        o_ref[...] = body(refs[0][...], row0)


def row_blocked_call(body, q, *, reach: int, interpret: bool, name: str):
    """Run a replicate-edge stencil over int32 ``q`` (B, H, W).

    ``body(window, row0)`` maps an int32 ``(rows, W')`` window whose
    first row is image row ``row0`` to the stencil output of the same
    shape; it must read at most ``reach`` rows away.  The plane is
    zero-padded to whole (8, 128) tiles and row blocks; ``body`` never
    reads the padding for an image position because its edge selects
    use the true extents, and the padding is sliced off."""
    b, h, w = q.shape
    wp = _round_up(w, _LANES)
    bh = _SUBLANES
    while bh * 2 * wp <= BLOCK_ELEMS:
        bh *= 2
    halo = 0
    if _round_up(h, _SUBLANES) <= bh:
        bh = _round_up(h, _SUBLANES)
    elif reach:
        halo = _SUBLANES
        while halo < reach:
            halo *= 2
        bh = max(bh, halo)
    nb = -(-h // bh)
    hp = nb * bh
    if (hp, wp) != (h, w):
        q = jnp.pad(q, ((0, 0), (0, hp - h), (0, wp - w)))
    block = pl.BlockSpec((None, bh, wp), lambda i, j: (i, j, 0))
    in_specs, args = [block], [q]
    if halo:
        per = bh // halo
        last = hp // halo - 1
        in_specs += [
            pl.BlockSpec((None, halo, wp),
                         lambda i, j: (i, jnp.maximum(j * per - 1, 0), 0)),
            pl.BlockSpec((None, halo, wp),
                         lambda i, j: (i, jnp.minimum((j + 1) * per, last),
                                       0)),
        ]
        args += [q, q]
    out = pl.pallas_call(
        functools.partial(_kernel, body=body, bh=bh, halo=halo),
        out_shape=jax.ShapeDtypeStruct((b, hp, wp), jnp.int32),
        grid=(b, nb),
        in_specs=in_specs,
        out_specs=block,
        interpret=interpret,
        name=name,
    )(*args)
    return out[:, :h, :w]

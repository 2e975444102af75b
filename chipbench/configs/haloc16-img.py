"""Plain reference of ``haloc16-img``: the blur -> sharpen -> downsample
chain on a 16-bit HALOC-AxA datapath, in the integer domain.

Each stage works on signed fixed-point values with its own number of
fractional bits.  Between stages the value is rounded half up to whole
grey levels, clipped to [0, 255] and rescaled to the next stage's
fractional bits; the last stage's value is rounded and clipped to uint8.
Every weighted sum is a left-to-right fold of the weighted terms (exact
products, taken modulo 2^16) through the configured adder, sign-extended
from 16 bits.  Filter taps replicate the image edge.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import refcore

#: (input, output) fractional bits of each stage.
FRAC = {"gaussian_blur": (3, 3), "sharpen": (3, 3), "downsample2x": (4, 4)}


def _fold(terms, weights, adder):
    n = adder["n_bits"]
    add = refcore.ADDERS[adder["kind"]]
    acc = None
    for t, w in zip(terms, weights):
        p = refcore.to_pattern(t * w, n)
        acc = p if acc is None else add(acc, p, n, adder.get("lsm_bits", 0),
                                        adder.get("const_bits", 0))
    return refcore.to_signed(acc, n)


def _shifted(q, axis: int, offset: int):
    """``out[..., i] = q[..., clip(i + offset, 0, n - 1)]`` along axis."""
    n = q.shape[axis]
    idx = jnp.clip(jnp.arange(n) + offset, 0, n - 1)
    return jnp.take(q, idx, axis=axis)


def _gauss3(q, adder):
    for axis in (-1, -2):
        taps = [_shifted(q, axis, o) for o in (-1, 0, 1)]
        q = refcore.round_shift(_fold(taps, (1, 2, 1), adder), 2)
    return q


def _stage(name, q, adder):
    if name == "gaussian_blur":
        return _gauss3(q, adder)
    if name == "sharpen":
        return _fold([q, _gauss3(q, adder)], (2, -1), adder)
    if name == "downsample2x":
        h, w = q.shape[-2] & ~1, q.shape[-1] & ~1
        q = q[..., :h, :w]
        phases = [q[..., 0::2, 0::2], q[..., 0::2, 1::2],
                  q[..., 1::2, 0::2], q[..., 1::2, 1::2]]
        return refcore.round_shift(_fold(phases, (1, 1, 1, 1), adder), 2)
    raise ValueError(f"the reference has no stage {name!r}")


def _pipeline(imgs, stages, adder):
    q = imgs.astype(jnp.int32) << FRAC[stages[0]][0]
    for i, name in enumerate(stages):
        q = _stage(name, q, adder)
        out = FRAC[name][1]
        q = jnp.clip(refcore.round_shift(q, out), 0, 255)
        if i + 1 < len(stages):
            q = q << FRAC[stages[i + 1]][0]
    return q.astype(jnp.uint8)


@functools.lru_cache(maxsize=None)
def _compiled(stages, adder_items):
    return jax.jit(functools.partial(_pipeline, stages=stages,
                                     adder=dict(adder_items)))


def reference(imgs, cfg):
    """uint8 (B, H, W) -> the pipeline's uint8 output, on the device."""
    fn = _compiled(tuple(cfg["pipeline"]),
                   tuple(sorted(cfg["adder"].items())))
    return fn(jnp.asarray(imgs))

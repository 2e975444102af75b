"""Per-kernel validation: shape/dtype sweeps, assert_allclose vs ref.py
oracles (interpret=True executes kernel bodies on CPU; TPU is the target).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.specs import AdderSpec, paper_spec
from repro.kernels import ops, ref

KINDS = ("haloc_axa", "loa", "m_herloa", "accurate")


def _spec(kind):
    return paper_spec(kind)


# ------------------------------------------------------------ approx_add --

@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("shape", [(256, 256), (64, 100), (3, 7, 11), (1000,)])
def test_approx_add_kernel(kind, shape):
    rng = np.random.default_rng(42)
    a = rng.integers(-(1 << 30), 1 << 30, size=shape, dtype=np.int32)
    b = rng.integers(-(1 << 30), 1 << 30, size=shape, dtype=np.int32)
    spec = _spec(kind)
    got = np.asarray(ops.approx_add(jnp.asarray(a), jnp.asarray(b), spec))
    want = ref.ref_approx_add(a, b, spec)
    np.testing.assert_array_equal(got, want)


def test_approx_add_kernel_matches_accurate():
    rng = np.random.default_rng(0)
    a = rng.integers(-1000, 1000, size=(128, 128), dtype=np.int32)
    b = rng.integers(-1000, 1000, size=(128, 128), dtype=np.int32)
    spec = AdderSpec(kind="accurate")
    got = np.asarray(ops.approx_add(jnp.asarray(a), jnp.asarray(b), spec))
    np.testing.assert_array_equal(got, a + b)


# --------------------------------------------------------- approx_matmul --

@pytest.mark.parametrize("kind", ("haloc_axa", "loa", "accurate"))
@pytest.mark.parametrize("mnk", [(128, 128, 256), (64, 96, 384), (32, 32, 128)])
def test_approx_matmul_kernel(kind, mnk):
    m, n, k = mnk
    rng = np.random.default_rng(1)
    a = rng.integers(-128, 128, size=(m, k), dtype=np.int8)
    b = rng.integers(-128, 128, size=(k, n), dtype=np.int8)
    spec = _spec(kind)
    block = (128, 128, 128)
    got = np.asarray(ops.approx_matmul(jnp.asarray(a), jnp.asarray(b), spec,
                                       block=block))
    want = ref.ref_approx_matmul(a, b, spec, bk=block[2])
    np.testing.assert_array_equal(got, want)


def test_approx_matmul_error_bounded():
    """Approximate accumulation stays within (#tiles-1) * lsm bound."""
    rng = np.random.default_rng(3)
    m, n, k = 64, 64, 512
    a = rng.integers(-128, 128, size=(m, k), dtype=np.int8)
    b = rng.integers(-128, 128, size=(k, n), dtype=np.int8)
    spec = _spec("haloc_axa")
    got = np.asarray(ops.approx_matmul(jnp.asarray(a), jnp.asarray(b), spec))
    exact = a.astype(np.int64) @ b.astype(np.int64)
    n_tiles = k // 128
    bound = (n_tiles - 1) * (1 << (spec.lsm_bits + 1))
    assert np.max(np.abs(got.astype(np.int64) - exact)) <= bound


# ------------------------------------------------------------- butterfly --

@pytest.mark.parametrize("kind", ("haloc_axa", "herloa", "accurate"))
@pytest.mark.parametrize("inverse", (False, True))
def test_butterfly_kernel(kind, inverse):
    rng = np.random.default_rng(5)
    rows, half = 256, 128
    lim = 1 << 24
    a_re = rng.integers(-lim, lim, size=(rows, half), dtype=np.int32)
    a_im = rng.integers(-lim, lim, size=(rows, half), dtype=np.int32)
    b_re = rng.integers(-lim, lim, size=(rows, half), dtype=np.int32)
    b_im = rng.integers(-lim, lim, size=(rows, half), dtype=np.int32)
    ang = -2 * np.pi * np.arange(half) / (2 * half)
    w_re = np.round(np.cos(ang) * (1 << 14)).astype(np.int32)
    w_im = np.round(np.sin(ang) * (1 << 14)).astype(np.int32)
    spec = _spec(kind)
    got = ops.butterfly(*(jnp.asarray(x) for x in
                          (a_re, a_im, b_re, b_im, w_re, w_im)),
                        spec, inverse=inverse)
    want = ref.ref_butterfly(a_re, a_im, b_re, b_im, w_re, w_im, spec,
                             inverse=inverse)
    for g, w in zip(got, want):
        np.testing.assert_array_equal(np.asarray(g), w)


def test_butterfly_matches_image_fft_stage():
    """The kernel agrees with the host FFT's butterfly math (image/fft)."""
    from repro.image import fft as F
    spec = _spec("haloc_axa")
    cfg = F.FixedFFTConfig(spec=spec, frac_bits=6)
    rng = np.random.default_rng(9)
    rows, half = 64, 8
    vals = rng.integers(-(1 << 20), 1 << 20, size=(4, rows, half))
    a_re, a_im, b_re, b_im = (v.astype(np.int32) for v in vals)
    ang = -2 * np.pi * np.arange(half) / (2 * half)
    w_re = np.round(np.cos(ang) * (1 << 14)).astype(np.int64)
    w_im = np.round(np.sin(ang) * (1 << 14)).astype(np.int64)
    m = np.uint64(0xFFFFFFFF)
    to_u = lambda x: x.astype(np.int64).astype(np.uint64) & m
    t_re, t_im = F._cmul(to_u(b_re), to_u(b_im), w_re, w_im, cfg)
    top_re = F._add(to_u(a_re), t_re, cfg)
    bot_re = F._sub(to_u(a_re), t_re, cfg)
    got = ops.butterfly(*(jnp.asarray(x) for x in
                          (a_re, a_im, b_re, b_im,
                           w_re.astype(np.int32), w_im.astype(np.int32))),
                        spec)
    from_u = lambda u: u.astype(np.uint32).astype(np.int32)
    np.testing.assert_array_equal(np.asarray(got[0]), from_u(top_re))
    np.testing.assert_array_equal(np.asarray(got[2]), from_u(bot_re))


# ------------------------------------------------ row-blocked stencils --

STENCIL16 = AdderSpec(kind="haloc_axa", n_bits=16, lsm_bits=8, const_bits=4)


@pytest.mark.parametrize("shape", [(2, 600, 200), (1, 520, 129)])
@pytest.mark.parametrize("op", ("filter_chain", "conv2d"))
def test_row_blocked_stencil_kernels(op, shape):
    """Planes too large for one VMEM block run as row blocks with an
    in-kernel halo (ragged H, unaligned W); the result equals the
    padding oracles of the jax/numpy backends bit for bit."""
    from repro.ax import get_backend
    from repro.ax.backends import FilterStage
    from repro.ax.mul import MulSpec
    from repro.kernels import stencil
    h, w = shape[-2:]
    wp = -(-w // 128) * 128
    assert h * wp > stencil.BLOCK_ELEMS, "shape must need several blocks"
    rng = np.random.default_rng(7)
    if op == "filter_chain":
        q = rng.integers(0, 255 * 8, size=shape).astype(np.int32)
        stages = (FilterStage(-2, (-2, -1, 0, 1, 2), (1, 4, 6, 4, 1), 4),
                  FilterStage(-1, (-1, 0, 1), (1, 2, 1), 2),
                  FilterStage(-2, (1, -1), (1, -1)))
        want = get_backend("jax").filter_chain(jnp.asarray(q), STENCIL16,
                                               stages, strategy="fused")
        got = get_backend("pallas").filter_chain(jnp.asarray(q), STENCIL16,
                                                 stages, strategy="fused")
    else:
        q = rng.integers(-255, 256, size=shape).astype(np.int32)
        mul = MulSpec("broken_array", 8, 3, 1)
        kernel = ((5, -7, 9), (11, 13, -6), (4, 17, 8))
        want = get_backend("numpy").conv2d(q, STENCIL16, mul, kernel,
                                           shift=3)
        got = get_backend("pallas").conv2d(jnp.asarray(q), STENCIL16, mul,
                                           kernel, shift=3,
                                           strategy="fused")
    np.testing.assert_array_equal(np.asarray(got),
                                  np.asarray(want).astype(np.int32))

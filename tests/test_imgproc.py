"""Tests for the ``repro.imgproc`` workload subsystem and the fused
multi-operand ``accumulate`` engine primitive it rides on.

Acceptance (ISSUE 2): every operator bit-identical between the numpy
reference engine and the jax backend for the accurate kind; all
registered adder kinds run through every operator; a batched (vmapped)
corpus sweep over >=4 images x >=6 operators x all TABLE1_KINDS with
PSNR/SSIM finite and the accurate adder lossless on add/blend.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.ax import make_engine
from repro.core.specs import ALL_KINDS, TABLE1_KINDS
from repro.imgproc import (
    OPERATORS,
    get_workload,
    make_image_engine,
    operator_names,
    run_corpus,
    synthetic_batch,
    workload_names,
)
from repro.numerics.fixed_point import FixedPointFormat

IMG = synthetic_batch(2, 32)
A, B = IMG[0], IMG[1]


def _args(op):
    return (A,) if op.n_inputs == 1 else (A, B)


# ------------------------------------------------ accumulate primitive --

@pytest.mark.parametrize("kind", ["accurate", "haloc_axa", "herloa"])
def test_accumulate_cross_backend_bit_identity(kind):
    fmt = FixedPointFormat(16, 3)
    rng = np.random.default_rng(3)
    q = rng.integers(-2000, 2000, (4, 9, 33)).astype(np.int32)
    outs = {}
    for backend in ("numpy", "jax", "pallas"):
        ax = make_engine(kind, fmt=fmt, backend=backend)
        outs[backend] = np.asarray(
            ax.accumulate_signed(q, (1, 2, 2, 1), shift=2))
    np.testing.assert_array_equal(outs["numpy"], outs["jax"])
    np.testing.assert_array_equal(outs["numpy"], outs["pallas"])


def test_accumulate_equals_sequential_adds():
    """The fused fold is bit-identical to K-1 chained add_signed calls
    with pre-scaled terms (same adder, same order)."""
    fmt = FixedPointFormat(16, 3)
    rng = np.random.default_rng(4)
    q = rng.integers(-2000, 2000, (3, 17)).astype(np.int32)
    for kind in ("accurate", "haloc_axa", "loa"):
        ax = make_engine(kind, fmt=fmt, backend="numpy")
        fused = ax.accumulate_signed(q, (1, 2, 1))
        acc = q[0]
        for term in (2 * q[1], q[2]):
            acc = ax.add_signed(acc, term.astype(np.int32))
        np.testing.assert_array_equal(np.asarray(fused), np.asarray(acc))


def test_accumulate_accurate_matches_exact_weighted_sum():
    fmt = FixedPointFormat(16, 0)
    rng = np.random.default_rng(5)
    q = rng.integers(-3000, 3000, (4, 25)).astype(np.int32)
    ax = make_engine("accurate", fmt=fmt, backend="jax")
    got = np.asarray(ax.accumulate_signed(q, (1, -2, 3, 1), shift=1))
    want = (q[0].astype(np.int64) - 2 * q[1] + 3 * q[2] + q[3] + 1) >> 1
    np.testing.assert_array_equal(got, want)


def test_scaled_add_matches_accumulate():
    fmt = FixedPointFormat(16, 2)
    rng = np.random.default_rng(6)
    qx = rng.integers(-2000, 2000, (8, 8)).astype(np.int32)
    qy = rng.integers(-2000, 2000, (8, 8)).astype(np.int32)
    ax = make_engine("haloc_axa", fmt=fmt, backend="jax")
    got = ax.scaled_add(jnp.asarray(qx), jnp.asarray(qy), 2, -1, shift=1)
    want = ax.accumulate_signed(jnp.stack([jnp.asarray(qx),
                                           jnp.asarray(qy)]),
                                (2, -1), shift=1)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))


def test_accumulate_weight_count_mismatch_raises():
    fmt = FixedPointFormat(16, 0)
    ax = make_engine("haloc_axa", fmt=fmt, backend="jax")
    with pytest.raises(ValueError, match="weights"):
        ax.accumulate_signed(jnp.zeros((3, 4), jnp.int32), (1, 1))


# ------------------------------------------------------- operators --

@pytest.mark.parametrize("name", operator_names())
def test_operator_numpy_jax_bit_identity_accurate(name):
    """Acceptance: numpy reference engine == jax backend, bit for bit,
    for the accurate kind, on every operator."""
    op = OPERATORS[name]
    out_np = np.asarray(op.fn(*_args(op),
                              make_image_engine("accurate",
                                                backend="numpy")))
    out_jx = np.asarray(op.fn(*_args(op),
                              make_image_engine("accurate", backend="jax")))
    np.testing.assert_array_equal(out_np, out_jx)


@pytest.mark.parametrize("name", operator_names())
def test_operator_pallas_jax_bit_identity(name):
    """The fused Pallas tile kernel path agrees with the jax emulation
    for an approximate kind too."""
    op = OPERATORS[name]
    out_pl = np.asarray(op.fn(*_args(op),
                              make_image_engine("haloc_axa",
                                                backend="pallas")))
    out_jx = np.asarray(op.fn(*_args(op),
                              make_image_engine("haloc_axa",
                                                backend="jax")))
    np.testing.assert_array_equal(out_pl, out_jx)


@pytest.mark.parametrize("kind", ALL_KINDS)
def test_every_kind_runs_every_operator(kind):
    """Acceptance: all registered adder kinds x all operators, no
    errors, valid uint8 output shapes."""
    ax = make_image_engine(kind, backend="jax")
    for op in OPERATORS.values():
        out = np.asarray(op.fn(*_args(op), ax))
        assert out.dtype == np.uint8
        want = A.shape if op.name != "downsample2x" else \
            (A.shape[0] // 2, A.shape[1] // 2)
        assert out.shape == want, (op.name, out.shape)


def test_operator_accurate_close_to_reference():
    """The accurate-adder fixed-point datapath lands within one gray
    level of the ideal float reference on every operator (the only
    discrepancy is the documented per-pass rounding)."""
    ax = make_image_engine("accurate", backend="jax")
    for op in OPERATORS.values():
        out = np.asarray(op.fn(*_args(op), ax)).astype(np.int64)
        ref = op.reference(*_args(op)).astype(np.int64)
        assert np.abs(out - ref).max() <= 1, op.name


def test_operators_batched_leading_dims():
    """Operators accept (..., H, W) batches natively."""
    ax = make_image_engine("haloc_axa", backend="jax")
    from repro.imgproc import box_blur
    single = np.asarray(box_blur(IMG[0], ax))
    batched = np.asarray(box_blur(IMG, ax))
    assert batched.shape == IMG.shape
    np.testing.assert_array_equal(batched[0], single)


# ---------------------------------------------------------- corpus --

def test_corpus_sweep_acceptance():
    """Acceptance: vmapped sweep over >=4 images x >=6 operators x all
    TABLE1_KINDS; PSNR/SSIM finite for approximate kinds; accurate
    lossless on add/blend."""
    batch = synthetic_batch(4, 32)
    rows = run_corpus(batch=batch, backend="jax")
    ops = {r.workload for r in rows}
    kinds = {r.kind for r in rows}
    assert len(ops) >= 6
    assert kinds == set(TABLE1_KINDS)
    assert len(rows) == len(ops) * len(kinds)
    for r in rows:
        assert np.isfinite(r.ssim), r
        if r.kind != "accurate":
            assert np.isfinite(r.psnr), r
        assert 0.0 < r.ssim <= 1.0, r
    by = {(r.kind, r.workload): r for r in rows}
    for name in ("add", "blend"):
        assert by[("accurate", name)].psnr == float("inf"), name
        assert by[("accurate", name)].ssim == 1.0, name


@pytest.mark.parametrize("backend", ("pallas", "pallas_tpu"))
def test_run_corpus_named_backend_reaches_its_kernels(backend,
                                                       monkeypatch):
    """A batched workload run with an explicitly named Pallas backend
    dispatches that backend's kernel entry, whatever the host's
    auto-detected default is.  (The kernel is stubbed: the Mosaic one
    runs on a TPU only.)"""
    from repro.ax import get_backend
    calls = []

    def stub(self, q, spec, stages, *, strategy="reference"):
        calls.append(self.name)
        return q

    monkeypatch.setattr(type(get_backend(backend)), "filter_chain", stub)
    rows = run_corpus(kinds=("haloc_axa",), workloads=("gaussian_blur",),
                      n_images=1, size=19, backend=backend)
    assert len(rows) == 1
    assert calls and set(calls) == {backend}


def test_corpus_quality_ordering():
    """The error-compensated families beat the plain OR families on the
    blur corpus cells, mirroring the paper's Fig-5/6 ordering."""
    rows = run_corpus(batch=synthetic_batch(2, 32),
                      workloads=("box_blur",), backend="jax")
    s = {r.kind: r.ssim for r in rows}
    assert s["herloa"] > s["loawa"]
    assert s["haloc_axa"] > s["loawa"]
    assert s["accurate"] >= max(v for k, v in s.items() if k != "accurate")


def test_corpus_workload_kw_is_per_workload():
    """Per-workload kwargs reach only their own cells; unknown names
    are rejected up front."""
    batch = synthetic_batch(2, 32)
    rows = run_corpus(kinds=("accurate",), batch=batch, backend="jax",
                      workloads=("blend", "box_blur"),
                      workload_kw={"blend": {"alpha": 0.25}})
    assert {r.workload for r in rows} == {"blend", "box_blur"}
    with pytest.raises(ValueError, match="workload_kw"):
        run_corpus(kinds=("accurate",), batch=batch,
                   workloads=("box_blur",),
                   workload_kw={"blend": {"alpha": 0.25}})


def test_operator_params_validate_headroom():
    """Out-of-range operator parameters raise instead of silently
    wrapping mod 2^16."""
    from repro.imgproc import blend, brightness, sharpen
    ax = make_image_engine("accurate", backend="jax")
    with pytest.raises(ValueError, match="alpha"):
        blend(A, B, ax, alpha=4.0)
    with pytest.raises(ValueError, match="amount"):
        sharpen(A, ax, amount=24)
    with pytest.raises(ValueError, match="delta"):
        brightness(A, ax, delta=4000.0)


def test_make_image_engine_rejects_wide_datapath():
    from repro.core.specs import paper_spec
    with pytest.raises(ValueError, match="n_bits <= 30"):
        make_image_engine("haloc_axa", n_bits=32)
    with pytest.raises(ValueError, match="n_bits <= 30"):
        make_image_engine(paper_spec("haloc_axa"))


# ------------------------------------------------------- workloads --

def test_workload_registry():
    names = workload_names()
    assert "fft_reconstruct" in names
    assert set(operator_names()) <= set(names)
    # batched_only drops the host FFT workload
    assert "fft_reconstruct" not in workload_names(batched_only=True)


def test_fft_reconstruct_workload_migrated():
    """The Fig-5 reconstruction runs as a registered imgproc workload."""
    wl = get_workload("fft_reconstruct")
    batch = synthetic_batch(2, 32)
    out = wl.run(batch, kind="accurate", block=16)
    ref = wl.reference(batch)
    assert out.shape == batch.shape and out.dtype == np.uint8
    from repro.image.quality import psnr
    assert min(psnr(r, o) for r, o in zip(ref, out)) > 40

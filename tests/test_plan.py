"""Tests for the compiled pipeline plans (``repro.imgproc.plan``), the
``filter_chain`` engine primitive, and the multi-stage Pallas conv
chain kernel behind it.

Acceptance (ISSUE 3): a compiled pipeline is bit-identical to its
stages run individually; plans round-trip through the compile cache;
the Pallas chain kernel matches the stage-by-stage jax/numpy paths; the
fori-loop matmul matches the unrolled host reference for ragged K.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.ax import FilterStage, get_adder, make_engine
from repro.core.specs import AdderSpec, paper_spec
from repro.imgproc import (
    PIPELINES,
    compile_pipeline,
    get_workload,
    run_pipeline,
    synthetic_batch,
)
from repro.numerics.fixed_point import FixedPointFormat

BATCH = synthetic_batch(3, 32)


def _sequential(stages, imgs, kind, backend="jax"):
    x = imgs
    for st in stages:
        name, kw = (st, {}) if isinstance(st, str) else st
        x = get_workload(name).run(x, kind=kind, backend=backend, **kw)
    return x


# ------------------------------------------------------------- plans --

@pytest.mark.parametrize("name", sorted(PIPELINES))
@pytest.mark.parametrize("kind", ["accurate", "haloc_axa"])
def test_compiled_pipeline_bit_identical_to_sequential(name, kind):
    stages = PIPELINES[name]
    fused = run_pipeline(stages, BATCH, kind=kind, backend="jax")
    np.testing.assert_array_equal(fused,
                                  _sequential(stages, BATCH, kind))
    assert fused.dtype == np.uint8


def test_pipeline_with_stage_kwargs():
    stages = (("gaussian_blur", {}), ("sharpen", {"amount": 2}))
    fused = run_pipeline(stages, BATCH, kind="haloc_axa", backend="jax")
    np.testing.assert_array_equal(
        fused, _sequential(stages, BATCH, "haloc_axa"))


def test_pipeline_shapes_through_downsample():
    out = run_pipeline(("gaussian_blur", "downsample2x", "downsample2x"),
                       BATCH, kind="haloc_axa", backend="jax")
    assert out.shape == (3, 8, 8)


def test_pipeline_compile_cache_round_trip():
    p1 = compile_pipeline(("box_blur", "sobel"), kind="haloc_axa",
                          backend="jax")
    p2 = compile_pipeline(["box_blur", ("sobel", {})], kind="haloc_axa",
                          backend="jax")
    assert p1 is p2
    assert p1.stage_names == ("box_blur", "sobel")
    p3 = compile_pipeline(("box_blur", "sobel"), kind="haloc_axa",
                          backend="jax", strategy="fused")
    assert p3 is not p1


@pytest.mark.parametrize("requant", ["stage", "fused"])
def test_plan_stages_scope_their_hlo_operations(requant):
    """Each stage runs under ``jax.named_scope("stage:<name>")``, so the
    compiled module's operations carry their stage in their metadata
    (and the device trace names the stage of each operation)."""
    import re

    import jax
    pipe = compile_pipeline(PIPELINES["pipe_blur_sharpen_down"],
                            kind="haloc_axa", backend="jax",
                            requant=requant)
    text = pipe.fn.lower(jax.ShapeDtypeStruct((2, 32, 32), jnp.uint8)
                         ).compile().as_text()
    scopes = set()
    for op_name in re.findall(r'op_name="([^"]*)"', text):
        scopes.update(re.findall(r"stage:\w+", op_name))
    assert scopes == {"stage:gaussian_blur", "stage:sharpen",
                      "stage:downsample2x"}


#: Even and odd planes, with none, one and two leading batch dims.
PHASE_SHAPES = [(8, 10), (7, 9), (3, 8, 11), (2, 9, 12), (2, 2, 7, 6)]


@pytest.mark.parametrize("array", ["numpy", "jax"])
@pytest.mark.parametrize("shape", PHASE_SHAPES, ids=str)
def test_phases2x_are_the_strided_views(shape, array):
    """The downsample's phase planes are ``x[..., i::2, j::2]`` of the
    even crop, in the order (0, 0), (0, 1), (1, 0), (1, 1), for numpy
    inputs and jax arrays alike."""
    from repro.imgproc.ops import phases2x
    x = np.arange(np.prod(shape), dtype=np.int32).reshape(shape)
    h, w = shape[-2] & ~1, shape[-1] & ~1
    planes = phases2x(jnp.asarray(x) if array == "jax" else x)
    assert len(planes) == 4
    for plane, (i, j) in zip(planes, ((0, 0), (0, 1), (1, 0), (1, 1))):
        np.testing.assert_array_equal(np.asarray(plane),
                                      x[..., :h, :w][..., i::2, j::2])


@pytest.fixture(scope="module")
def img_reference():
    """The ``haloc16-img`` configuration's plain reference, for any
    chain of its stages."""
    import json
    import os

    from chipbench.cells import load_module
    base = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench", "configs")
    ref = load_module(os.path.join(base, "haloc16-img.py"),
                      "haloc16_img_ref")
    with open(os.path.join(base, "haloc16-img.json")) as f:
        cfg = json.load(f)
    return lambda imgs, stages: np.asarray(ref.reference(
        imgs, dict(cfg, pipeline=list(stages))))


@pytest.mark.parametrize("backend", ["jax", "numpy"])
@pytest.mark.parametrize("stages", [("downsample2x",),
                                    PIPELINES["pipe_blur_sharpen_down"]],
                         ids=["downsample2x", "pipe_blur_sharpen_down"])
@pytest.mark.parametrize("shape", PHASE_SHAPES, ids=str)
def test_downsample_bit_identical_to_plain_reference(shape, stages,
                                                     backend,
                                                     img_reference):
    """``downsample2x`` alone and the blur -> sharpen -> downsample
    chain (fused requant) equal the benchmark's plain integer reference
    bit for bit for HALOC-AxA N=16, on even and odd planes with any
    leading batch dims."""
    imgs = np.random.default_rng(sum(shape)).integers(
        0, 256, shape, dtype=np.uint8)
    want = img_reference(imgs, stages)
    if stages == ("downsample2x",):
        from repro.imgproc import downsample2x
        from repro.imgproc.ops import make_image_engine
        got = downsample2x(imgs, make_image_engine("haloc_axa",
                                                   backend=backend))
    else:
        pipe = compile_pipeline(stages, kind="haloc_axa", backend=backend,
                                requant="fused")
        got = pipe.chain(imgs)
    got = np.asarray(got)
    assert got.dtype == np.uint8 and got.shape == want.shape
    np.testing.assert_array_equal(got, want)


def test_pipeline_numpy_backend_matches_jax():
    stages = PIPELINES["pipe_blur_sobel"]
    out_np = run_pipeline(stages, BATCH, kind="haloc_axa",
                          backend="numpy")
    out_jx = run_pipeline(stages, BATCH, kind="haloc_axa", backend="jax")
    np.testing.assert_array_equal(out_np, out_jx)


def test_pipeline_rejects_binary_and_empty():
    with pytest.raises(ValueError, match="unary"):
        compile_pipeline(("gaussian_blur", "blend"))
    with pytest.raises(ValueError, match="empty"):
        compile_pipeline(())
    with pytest.raises(KeyError):
        compile_pipeline(("no_such_op",))


def test_pipeline_workloads_registered():
    from repro.imgproc import workload_names
    names = workload_names(batched_only=True)
    for name in PIPELINES:
        assert name in names


# ------------------------------------------------------ filter_chain --

STAGES = (FilterStage(-1, (-1, 0, 1), (1, 2, 1), 2),
          FilterStage(-2, (-1, 0, 1), (1, 2, 1), 2),
          FilterStage(-1, (1, -1), (1, -1)))


@pytest.mark.parametrize("kind", ["accurate", "haloc_axa", "herloa"])
def test_filter_chain_cross_backend_bit_identity(kind):
    fmt = FixedPointFormat(16, 3)
    rng = np.random.default_rng(9)
    q = rng.integers(-2000, 2000, (2, 9, 33)).astype(np.int32)
    outs = {}
    for backend in ("numpy", "jax", "pallas"):
        ax = make_engine(kind, fmt=fmt, backend=backend)
        outs[backend] = np.asarray(ax.filter_chain(q, STAGES))
    np.testing.assert_array_equal(outs["numpy"], outs["jax"])
    np.testing.assert_array_equal(outs["numpy"], outs["pallas"])


def test_filter_chain_equals_stagewise_accumulate():
    """One chain call == stage-by-stage accumulate_signed folds."""
    fmt = FixedPointFormat(16, 3)
    rng = np.random.default_rng(10)
    q = rng.integers(-2000, 2000, (7, 21)).astype(np.int32)
    ax = make_engine("haloc_axa", fmt=fmt, backend="numpy")
    got = np.asarray(ax.filter_chain(q, STAGES))
    x = q
    for st in STAGES:
        axis = st.axis % x.ndim
        left = max(-min(st.offsets), 0)
        right = max(max(st.offsets), 0)
        pad = [(0, 0)] * x.ndim
        pad[axis] = (left, right)
        p = np.pad(x, pad, mode="edge")
        n = x.shape[axis]
        sl = [slice(None)] * x.ndim
        taps = []
        for o in st.offsets:
            s = list(sl)
            s[axis] = slice(o + left, o + left + n)
            taps.append(p[tuple(s)])
        x = np.asarray(ax.accumulate_signed(np.stack(taps), st.weights,
                                            shift=st.shift))
    np.testing.assert_array_equal(got, x)


def test_filter_chain_pallas_unbatched_and_strategy():
    fmt = FixedPointFormat(16, 3)
    rng = np.random.default_rng(12)
    q = rng.integers(-2000, 2000, (9, 33)).astype(np.int32)
    want = np.asarray(make_engine("haloc_axa", fmt=fmt,
                                  backend="jax").filter_chain(q, STAGES))
    for strategy in ("reference", "fused"):
        ax = make_engine("haloc_axa", fmt=fmt, backend="pallas",
                         strategy=strategy)
        np.testing.assert_array_equal(
            np.asarray(ax.filter_chain(jnp.asarray(q), STAGES)), want)


def test_filter_chain_pallas_rejects_batch_axis_taps():
    from repro.kernels.conv_chain import filter_chain_pallas
    q = jnp.zeros((2, 8, 8), jnp.int32)
    spec = AdderSpec(kind="haloc_axa", n_bits=16, lsm_bits=8, const_bits=4)
    with pytest.raises(ValueError, match="axis"):
        filter_chain_pallas(q, spec, (FilterStage(0, (0,), (1,)),),
                            interpret=True)


# --------------------------------------- satellite: strategies wired --

def test_fused_variants_registered_for_or_families():
    """LOA / LOAWA / OLOCA carry registered fused impls, so fast=True
    is no longer a HALOC-only special case (bit-identity is enforced
    by the exhaustive sweeps in test_ax.py / test_lut.py)."""
    for kind in ("loa", "loawa", "oloca", "haloc_axa"):
        assert get_adder(kind).fast_impl is not None, kind


def test_pallas_accumulate_honors_fast():
    """The fast flag reaches the Pallas kernel bodies (it was silently
    dropped before): the fused fold stays bit-identical."""
    fmt = FixedPointFormat(16, 2)
    rng = np.random.default_rng(13)
    q = rng.integers(-2000, 2000, (3, 9, 17)).astype(np.int32)
    outs = []
    for strategy in ("reference", "fused"):
        ax = make_engine("haloc_axa", fmt=fmt, backend="pallas",
                         strategy=strategy)
        outs.append(np.asarray(ax.accumulate_signed(q, (1, 2, 1),
                                                    shift=1)))
    np.testing.assert_array_equal(outs[0], outs[1])


def test_matmul_strategies_across_backends():
    """matmul honors the strategy everywhere: fused is bit-identical on
    numpy/jax/pallas, and lut raises (rather than silently running the
    reference form) on the host/Pallas oracles."""
    rng = np.random.default_rng(21)
    a = rng.integers(-128, 128, size=(16, 160), dtype=np.int8)
    b = rng.integers(-128, 128, size=(160, 16), dtype=np.int8)
    spec = paper_spec("haloc_axa")
    want = np.asarray(make_engine(spec, backend="numpy").matmul(a, b))
    for backend in ("numpy", "jax", "pallas"):
        got = make_engine(spec, backend=backend,
                          strategy="fused").matmul(a, b)
        np.testing.assert_array_equal(np.asarray(got), want)
    np.testing.assert_array_equal(
        np.asarray(make_engine(spec, backend="jax",
                               strategy="lut").matmul(a, b)), want)
    for backend in ("numpy", "pallas"):
        with pytest.raises(NotImplementedError, match="lut"):
            make_engine(spec, backend=backend, strategy="lut").matmul(a, b)


def test_pipeline_workload_rejects_stray_kwargs():
    from repro.imgproc import get_workload
    wl = get_workload("pipe_blur_sharpen_down")
    with pytest.raises(ValueError, match="kwargs"):
        wl.run(BATCH, kind="accurate", backend="jax", amount=2)
    with pytest.raises(ValueError, match="kwargs"):
        wl.reference(BATCH, amount=2)


def test_pallas_lut_limited_to_elementwise_add():
    fmt = FixedPointFormat(16, 0)
    ax = make_engine("haloc_axa", fmt=fmt, backend="pallas",
                     strategy="lut")
    with pytest.raises(NotImplementedError, match="lut"):
        ax.accumulate_signed(jnp.zeros((2, 8, 8), jnp.int32))
    with pytest.raises(NotImplementedError, match="lut"):
        ax.filter_chain(jnp.zeros((8, 8), jnp.int32),
                        (FilterStage(-1, (0,), (1,)),))


# ------------------------------------- satellite: fori-loop matmul --

@pytest.mark.parametrize("k", [64, 256, 300, 100])
def test_jax_matmul_fori_matches_unrolled_reference(k):
    """The lax.fori_loop K-tile loop (incl. ragged zero-padded last
    tile) is bit-identical to the unrolled short-slice host form."""
    rng = np.random.default_rng(k)
    a = rng.integers(-128, 128, size=(16, k), dtype=np.int8)
    b = rng.integers(-128, 128, size=(k, 24), dtype=np.int8)
    spec = paper_spec("haloc_axa")
    want = np.asarray(make_engine(spec, backend="numpy").matmul(a, b))
    got = np.asarray(make_engine(spec, backend="jax").matmul(
        jnp.asarray(a), jnp.asarray(b)))
    np.testing.assert_array_equal(got, want)

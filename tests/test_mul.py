"""Tests for the approximate multiplier family + MAC engine (ISSUE 6).

Acceptance:

- every registered multiplier kind is bit-identical across the
  numpy/jax/pallas backends and the reference/fused/lut strategies on
  an exhaustive N=8 operand sweep, for representative knob settings;
- the exact analytics (``exact_mul_error_metrics``) match brute-force
  enumeration (``exhaustive_mul_error_metrics``) bit-for-bit across the
  whole N=8 design space, and the closed form matches the compose path
  exactly where both apply;
- the MAC datapaths (``engine.conv2d``, MAC ``engine.matmul``) are
  cross-backend bit-identical, including ragged-K tiling and negative
  weights/operands;
- ``MacSpec`` / ``make_engine(mul=...)`` construction, caching, and
  validation behave as documented, and plugin kinds round-trip through
  the registry.

Exhaustive sweeps beyond 4^8 pairs carry ``@pytest.mark.slow`` and are
deselected from the tier-1 run (``pytest -m slow`` runs them).
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.ax import make_engine
from repro.ax.analytics import (
    exact_mul_error_metrics,
    exact_mul_error_metrics_sweep,
    mul_analytics_supported,
    mul_design_space,
)
from repro.ax.backends import get_backend
from repro.ax.mul import (
    MacSpec,
    MulSpec,
    approx_mul,
    compile_mul_lut,
    default_mul_spec,
    lut_mul,
    mul_error_delta_table,
    mul_lut_supported,
    register_multiplier,
    registered_multipliers,
    signed_mul_table,
    tap_tables,
    unregister_multiplier,
)
from repro.core.metrics import exhaustive_mul_error_metrics
from repro.core.specs import AdderSpec, paper_spec
from repro.numerics.fixed_point import FixedPointFormat

#: Representative knob settings: every kind, pruning off/mid/extreme.
CONFIGS = [
    MulSpec("accurate", 8),
    MulSpec("truncated", 8, 4),
    MulSpec("truncated", 8, 8),
    MulSpec("broken_array", 8, 4, 2),
    MulSpec("broken_array", 8, 0, 4),
    MulSpec("mitchell", 8),
    MulSpec("mitchell", 8, 3),
]

ADDER16 = AdderSpec(kind="haloc_axa", n_bits=16, lsm_bits=8, const_bits=4)
FMT16 = FixedPointFormat(16, 0)
KERNEL = ((1, 3, 1), (3, -5, 3), (1, 3, 1))


def _exhaustive_pairs(n_bits):
    vals = np.arange(1 << n_bits, dtype=np.uint64)
    return np.repeat(vals, 1 << n_bits), np.tile(vals, 1 << n_bits)


# ------------------------------------------------------------ registry --

def test_builtin_kinds_registered_in_order():
    kinds = registered_multipliers()
    assert kinds == ("accurate", "truncated", "broken_array", "mitchell")


def test_register_unregister_roundtrip():
    @register_multiplier("test_floor_half", order=99)
    def floor_half_mul(a, b, spec):
        return (a * b) - ((a * b) & ((a ^ a) + 1))

    try:
        assert "test_floor_half" in registered_multipliers()
        spec = MulSpec("test_floor_half", 4)
        a, b = _exhaustive_pairs(4)
        got = approx_mul(a, b, spec)
        np.testing.assert_array_equal(got, (a * b) & ~np.uint64(1))
        # re-registering the SAME impl is idempotent; a DIFFERENT one
        # collides
        register_multiplier("test_floor_half", order=99)(floor_half_mul)
        with pytest.raises(ValueError, match="already registered"):
            register_multiplier("test_floor_half")(lambda a, b, s: a)
    finally:
        unregister_multiplier("test_floor_half")
    assert "test_floor_half" not in registered_multipliers()
    with pytest.raises(ValueError, match="unknown multiplier"):
        MulSpec("test_floor_half", 4)


def test_spec_validation():
    with pytest.raises(ValueError, match="unknown multiplier"):
        MulSpec("nope", 8)
    with pytest.raises(ValueError, match="n_bits"):
        MulSpec("truncated", 16)
    with pytest.raises(ValueError, match="trunc_bits"):
        MulSpec("truncated", 8, 9)
    with pytest.raises(ValueError, match="trunc_bits"):
        MulSpec("mitchell", 8, 8)       # trunc_margin=1: t <= 7
    with pytest.raises(ValueError, match="row_bits"):
        MulSpec("truncated", 8, 0, 2)   # rows only for broken_array
    assert MulSpec("mitchell", 8, 7).effective_trunc_bits == 7
    assert MulSpec("accurate", 8, 0).is_exact
    mac = MacSpec(ADDER16, MulSpec("truncated", 8, 4))
    assert mac.short_name == f"{ADDER16.short_name}+truncated-n8t4"
    with pytest.raises(TypeError, match="AdderSpec"):
        MacSpec(MulSpec("accurate", 8), MulSpec("accurate", 8))


# ----------------------------------------- cross-backend bit identity --

@pytest.mark.parametrize("spec", CONFIGS, ids=lambda s: s.short_name)
def test_mul_bit_identical_exhaustive_n8(spec):
    """Every backend x strategy agrees with the numpy reference on all
    4^8 operand pairs (the Pallas backends have no lut strategy)."""
    a, b = _exhaustive_pairs(8)
    want = get_backend("numpy").mul(a, b, spec, strategy="reference")
    want = np.asarray(want).astype(np.int64)
    aj = jnp.asarray(a.astype(np.int32))
    bj = jnp.asarray(b.astype(np.int32))
    for backend in ("numpy", "jax", "pallas"):
        be = get_backend(backend)
        x, y = (a, b) if backend == "numpy" else (aj, bj)
        for strategy in ("reference", "fused", "lut"):
            if backend == "pallas" and strategy == "lut":
                continue
            got = np.asarray(be.mul(x, y, spec, strategy=strategy))
            np.testing.assert_array_equal(
                got.astype(np.int64), want,
                err_msg=f"{spec.short_name} {backend}/{strategy}")


@pytest.mark.parametrize("spec", CONFIGS, ids=lambda s: s.short_name)
def test_underestimate_and_zero_annihilation(spec):
    """Builtin kinds never overestimate, and a zero operand always
    yields zero (the MAC paths zero-pad ragged K tiles on this)."""
    a, b = _exhaustive_pairs(8)
    got = approx_mul(a, b, spec).astype(np.int64)
    exact = (a * b).astype(np.int64)
    assert (got <= exact).all()
    assert (got[(a == 0) | (b == 0)] == 0).all()


def test_fused_equals_reference_beyond_lut_width():
    """fused == reference at N=12 (no LUT exists there) on random
    operands, numpy and jax."""
    rng = np.random.default_rng(3)
    a = rng.integers(0, 1 << 12, size=20000, dtype=np.uint64)
    b = rng.integers(0, 1 << 12, size=20000, dtype=np.uint64)
    for spec in (MulSpec("truncated", 12, 5),
                 MulSpec("broken_array", 12, 6, 3),
                 MulSpec("mitchell", 12)):
        ref = approx_mul(a, b, spec).astype(np.int64)
        np.testing.assert_array_equal(
            approx_mul(a, b, spec, fast=True).astype(np.int64), ref)
        got = get_backend("jax").mul(jnp.asarray(a.astype(np.int32)),
                                     jnp.asarray(b.astype(np.int32)),
                                     spec, strategy="fused")
        np.testing.assert_array_equal(np.asarray(got).astype(np.int64),
                                      ref)


def test_lut_tables_cached_and_readonly():
    spec = MulSpec("truncated", 8, 4)
    t1 = compile_mul_lut(spec)
    t2 = compile_mul_lut(MulSpec("truncated", 8, 4))
    assert t1 is t2
    assert not t1.flags.writeable
    assert not signed_mul_table(spec).flags.writeable
    assert not mul_error_delta_table(spec).flags.writeable
    # lut strategy beyond the compile cap refuses instead of lying
    wide = MulSpec("truncated", 12, 4)
    assert not mul_lut_supported(wide)
    with pytest.raises(ValueError, match="LUT"):
        lut_mul(np.uint64([1]), np.uint64([2]), wide)
    with pytest.raises(NotImplementedError, match="product table"):
        get_backend("pallas").mul(jnp.int32([1]), jnp.int32([2]), wide,
                                  strategy="lut")


def test_tap_tables_reject_wide_weights():
    with pytest.raises(ValueError, match="weight"):
        tap_tables(MulSpec("truncated", 8, 4), (1, 256))


# ------------------------------------------------------------ analytics --

def test_analytics_match_enumeration_full_design_space_n8():
    """Exact analytics == brute-force enumeration, bit-for-bit, on every
    point of the N=8 multiplier design space."""
    specs = mul_design_space(n_bits=(8,))
    assert len(specs) > 40
    reports = exact_mul_error_metrics_sweep(specs, cache_tables=False)
    for spec, rep in zip(specs, reports):
        assert mul_analytics_supported(spec)
        brute = exhaustive_mul_error_metrics(spec)
        for field in ("med", "mred", "nmed", "error_rate", "wce",
                      "n_samples"):
            assert getattr(rep, field) == getattr(brute, field), \
                f"{spec.short_name}.{field}"


def test_closed_form_equals_compose():
    """The low-delta closed form and the full-table compose path return
    the SAME floats (identical canonical reduction), where both apply."""
    for spec in (MulSpec("truncated", 8, 4), MulSpec("truncated", 8, 8),
                 MulSpec("broken_array", 8, 5, 0),
                 MulSpec("truncated", 10, 6)):
        closed = exact_mul_error_metrics(spec, method="closed")
        compose = exact_mul_error_metrics(spec, method="compose")
        for field in ("med", "mred", "nmed", "error_rate", "wce"):
            assert getattr(closed, field) == getattr(compose, field), \
                f"{spec.short_name}.{field}"


def test_closed_form_beyond_enumeration():
    """Closed form prices a width whose 4^N domain could never be
    enumerated (N=15: 10^9 pairs), with enumeration-free sanity."""
    rep = exact_mul_error_metrics(MulSpec("truncated", 15, 7),
                                  method="closed")
    assert rep.med > 0 and 0 < rep.error_rate < 1
    assert 0 < rep.mred < 1e-3
    assert rep.wce == sum(1 << (i + j) for i in range(7)
                          for j in range(7 - i))


def test_mitchell_mred_matches_literature():
    """Mitchell's classic worst-case/average figures: MRED ~3.8% and
    maximum relative error 1 - 3*ln(2)/e < 11.1%."""
    rep = exact_mul_error_metrics(MulSpec("mitchell", 8))
    assert abs(rep.mred - 0.0376) < 2e-3
    a, b = _exhaustive_pairs(8)
    exact = (a * b).astype(np.float64)
    got = approx_mul(a, b, MulSpec("mitchell", 8)).astype(np.float64)
    nz = exact > 0
    assert ((exact[nz] - got[nz]) / exact[nz]).max() < 0.1112


def test_strategies_share_one_error_report():
    spec = MulSpec("mitchell", 8, 2)
    ref = exhaustive_mul_error_metrics(spec, strategy="reference")
    for strategy in ("fused", "lut"):
        got = exhaustive_mul_error_metrics(spec, strategy=strategy)
        assert got.row() == ref.row()


# --------------------------------------------------------- MAC datapaths --

def _pruned_array_specs(max_bits=8):
    """Every truncated and broken_array spec at n_bits <= max_bits."""
    for n in range(2, max_bits + 1):
        for t in range(n + 1):
            yield MulSpec("truncated", n, t)
            for v in range(n + 1):
                yield MulSpec("broken_array", n, t, v)


def test_plane_groups_identity_exhaustive():
    """The MXU form's identity, for every signed operand pair of every
    truncated and broken_array spec at n_bits <= 8: the grouped plane
    products sum to sign(a) sign(b) approx(|a|, |b|) exactly, and every
    plane factor fits int8."""
    from repro.kernels.mac import plane_groups
    for spec in _pruned_array_specs():
        w = spec.n_bits
        vals = np.arange(-(1 << (w - 1)), 1 << (w - 1), dtype=np.int64)
        sa, sb = np.repeat(vals, vals.size), np.tile(vals, vals.size)
        want = np.sign(sa) * np.sign(sb) * approx_mul(
            np.abs(sa).astype(np.uint64), np.abs(sb).astype(np.uint64),
            spec).astype(np.int64)
        got = np.zeros_like(want)
        groups = plane_groups(spec)
        assert groups, spec
        for cut, rows in groups:
            lhs = np.sign(sa) * (np.abs(sa) & -(1 << cut))
            rhs = np.sign(sb) * (np.abs(sb) & rows)
            for f in (lhs, rhs):
                assert f.min() >= -128 and f.max() <= 127, spec
            got += lhs * rhs
        np.testing.assert_array_equal(got, want, err_msg=spec.short_name)


def test_plane_groups_select_the_form():
    """Truncated n8t4 runs five plane matmuls a K tile; Mitchell and
    operands wider than int8 keep the column loop."""
    from repro.kernels.mac import plane_groups
    assert plane_groups(MulSpec("truncated", 8, 4)) == (
        (4, 0b1), (3, 0b10), (2, 0b100), (1, 0b1000), (0, 0b11110000))
    assert plane_groups(MulSpec("accurate", 8)) == ((0, 0xFF),)
    assert plane_groups(MulSpec("broken_array", 8, 0, 3)) == ((3, 0xFF),)
    assert plane_groups(MulSpec("mitchell", 8)) is None
    assert plane_groups(MulSpec("truncated", 9, 4)) is None


def _mac_operands(case):
    """(a, b) of a MAC GEMM test case: seeded random signed operands,
    or every element at one value."""
    shape_k, fill, dtype = case
    m, k, n = shape_k
    if fill is not None:
        return (np.full((m, k), fill, np.int8),
                np.full((k, n), fill, np.int8))
    rng = np.random.default_rng(21)
    lo, hi = np.iinfo(dtype).min, np.iinfo(dtype).max
    lo, hi = max(lo, -512), min(hi, 512)
    return (rng.integers(lo, hi, size=(m, k)).astype(dtype),
            rng.integers(lo, hi, size=(k, n)).astype(dtype))


@pytest.mark.parametrize("mul,case,drops", [
    pytest.param(MulSpec("truncated", 8, 3), ((16, 300, 24), None, np.int8),
                 True, id="truncated_n8t3_k_ragged"),
    pytest.param(MulSpec("truncated", 8, 3), ((16, 256, 24), None, np.int8),
                 True, id="truncated_n8t3_k_whole_tiles"),
    pytest.param(MulSpec("truncated", 8, 4), ((16, 300, 24), None, np.int8),
                 True, id="truncated_n8t4"),
    pytest.param(MulSpec("broken_array", 8, 5, 2),
                 ((16, 300, 24), None, np.int8), True,
                 id="broken_array_t5v2"),
    pytest.param(MulSpec("truncated", 6, 2), ((16, 300, 24), None, np.int32),
                 True, id="truncated_n6_wrap"),
    pytest.param(MulSpec("truncated", 8, 4), ((16, 256, 24), -128, None),
                 False, id="all_minus_128"),
    pytest.param(MulSpec("truncated", 8, 4), ((16, 256, 24), 127, None),
                 True, id="all_127"),
    pytest.param(MulSpec("mitchell", 8), ((16, 200, 24), None, np.int8),
                 True, id="mitchell_columns"),
])
def test_mac_matmul_bit_identical_across_backends(mul, case, drops):
    """MAC GEMM (approximate products + approximate accumulation) is
    bit-identical on numpy/jax/pallas — the Pallas kernel on its MXU
    plane form or, for Mitchell, its column loop — with inter-tile
    approximate folds exercised, and (where the multiplier drops mass
    at these operands) differs from the exact-product path."""
    a, b = _mac_operands(case)
    for spec in (paper_spec("haloc_axa"), ADDER16):
        want = np.asarray(get_backend("numpy").matmul(
            a, b, spec, strategy="reference", mul_spec=mul))
        for backend in ("numpy", "jax", "pallas"):
            for strategy in ("reference", "fused"):
                got = get_backend(backend).matmul(
                    a, b, spec, strategy=strategy, mul_spec=mul)
                np.testing.assert_array_equal(
                    np.asarray(got), want,
                    err_msg=f"{spec.short_name} {backend}/{strategy}")
        got = get_backend("jax").matmul(a, b, spec, strategy="lut",
                                        mul_spec=mul)
        np.testing.assert_array_equal(np.asarray(got), want)
        if drops:
            exact = np.asarray(get_backend("numpy").matmul(a, b, spec))
            assert not np.array_equal(exact, want)


@pytest.mark.parametrize("mul,form,dots", [
    (MulSpec("truncated", 8, 4), "mxu_planes", 5),
    (MulSpec("mitchell", 8), "vpu_columns", 0),
])
def test_mac_matmul_span_names_its_form(mul, form, dots, capture):
    """With telemetry on, the ``ax:matmul`` span of a MAC engine on the
    Pallas backend says which form the kernel took and how many plane
    matmuls it runs a K tile."""
    from repro import obs
    engine = make_engine(MacSpec(paper_spec("haloc_axa"), mul),
                         backend="pallas")
    a = np.ones((8, 128), np.int8)
    obs.reset_all()
    obs.enable()
    try:
        with capture() as cap:
            engine.matmul(a, a.T).block_until_ready()
    finally:
        obs.disable()
        obs.reset_all()
    span, = cap.named("ax:matmul")
    assert span.stats["form"] == form
    assert span.stats["mxu_dots"] == dots


def test_mac_matmul_exact_mul_spec_is_backcompat():
    """mul_spec=None and an exact MulSpec both take the existing
    exact-product path."""
    rng = np.random.default_rng(5)
    a = rng.integers(-128, 128, size=(16, 160), dtype=np.int8)
    b = rng.integers(-128, 128, size=(160, 16), dtype=np.int8)
    spec = paper_spec("haloc_axa")
    want = np.asarray(get_backend("numpy").matmul(a, b, spec))
    got = np.asarray(get_backend("numpy").matmul(
        a, b, spec, mul_spec=MulSpec("accurate", 8)))
    np.testing.assert_array_equal(got, want)


def test_conv2d_bit_identical_across_backends():
    """2D MAC convolution with signed inputs AND a negative tap weight:
    numpy/jax/pallas x reference/fused (+ jax lut) all agree."""
    rng = np.random.default_rng(11)
    q = rng.integers(-255, 256, size=(3, 17, 29)).astype(np.int32)
    mul = MulSpec("broken_array", 8, 3, 1)
    want = np.asarray(get_backend("numpy").conv2d(
        q, ADDER16, mul, KERNEL, shift=2, strategy="reference"))
    for backend in ("numpy", "jax", "pallas"):
        for strategy in ("reference", "fused"):
            got = get_backend(backend).conv2d(
                jnp.asarray(q) if backend != "numpy" else q,
                ADDER16, mul, KERNEL, shift=2, strategy=strategy)
            np.testing.assert_array_equal(
                np.asarray(got), want,
                err_msg=f"{backend}/{strategy}")
    got = get_backend("jax").conv2d(jnp.asarray(q), ADDER16, mul, KERNEL,
                                    shift=2, strategy="lut")
    np.testing.assert_array_equal(np.asarray(got), want)
    with pytest.raises(NotImplementedError, match="lut"):
        get_backend("pallas").conv2d(jnp.asarray(q), ADDER16, mul,
                                     KERNEL, shift=2, strategy="lut")


def test_conv2d_exact_mac_is_exact_convolution():
    """accurate adder + accurate multiplier reproduce the true integer
    convolution (replicate padding, rounded shift) exactly."""
    rng = np.random.default_rng(2)
    q = rng.integers(0, 256, size=(2, 9, 9)).astype(np.int32)
    eng = make_engine("accurate", fmt=FMT16, backend="jax",
                      mul=MulSpec("accurate", 8))
    got = np.asarray(eng.conv2d(q, KERNEL, shift=3))
    x = q.astype(np.int64)
    p = np.pad(x, ((0, 0), (1, 1), (1, 1)), mode="edge")
    acc = np.zeros_like(x)
    for dy in range(3):
        for dx in range(3):
            acc += KERNEL[dy][dx] * p[:, dy:dy + 9, dx:dx + 9]
    np.testing.assert_array_equal(got, (acc + 4) >> 3)


# ------------------------------------------------------------- engine --

def test_make_engine_mul_paths_and_caching():
    mul = MulSpec("truncated", 8, 3)
    e1 = make_engine(ADDER16, fmt=FMT16, backend="jax", mul=mul)
    e2 = make_engine(MacSpec(ADDER16, mul), fmt=FMT16, backend="jax")
    assert e1 is e2
    e3 = make_engine(ADDER16, fmt=FMT16, backend="jax", mul="truncated")
    assert e3.mul_spec == default_mul_spec("truncated")
    assert e1.replace(mul=None).mul_spec is None
    with pytest.raises(ValueError, match="not both"):
        make_engine(MacSpec(ADDER16, mul), fmt=FMT16, mul=mul)
    with pytest.raises(ValueError, match="unknown multiplier"):
        make_engine(ADDER16, fmt=FMT16, mul="nope")
    with pytest.raises(ValueError, match="LUT"):
        make_engine(ADDER16, fmt=FMT16, strategy="lut",
                    mul=MulSpec("truncated", 12, 4))


def test_engine_requires_mul_spec_for_mac_ops():
    eng = make_engine(ADDER16, fmt=FMT16, backend="numpy")
    with pytest.raises(ValueError, match="multiplier"):
        eng.mul(np.uint64([1]), np.uint64([2]))
    with pytest.raises(ValueError, match="multiplier"):
        eng.conv2d(np.zeros((4, 4), np.int32), KERNEL)


def test_engine_mul_signed_sign_magnitude():
    eng = make_engine(ADDER16, backend="numpy",
                      mul=MulSpec("truncated", 8, 4))
    qa = np.int64([-7, 7, -7, 0, -128])
    qb = np.int64([-9, 9, 9, -5, 3])
    got = eng.mul_signed(qa, qb)
    mag = approx_mul(np.abs(qa).astype(np.uint64),
                     np.abs(qb).astype(np.uint64),
                     MulSpec("truncated", 8, 4)).astype(np.int64)
    want = np.where((qa < 0) != (qb < 0), -mag, mag)
    np.testing.assert_array_equal(got, want)


def test_conv3x3_workload_cross_backend():
    from repro.imgproc.corpus import synthetic_batch
    from repro.imgproc.workloads import get_workload
    wl = get_workload("conv3x3")
    batch = synthetic_batch(2, 32)
    ref = wl.reference(batch)
    base = wl.run(batch, kind="haloc_axa", backend="numpy")
    for backend in ("jax", "pallas"):
        np.testing.assert_array_equal(
            wl.run(batch, kind="haloc_axa", backend=backend), base)
    exact = wl.run(batch, kind="accurate", backend="jax",
                   mul=MulSpec("accurate", 8))
    np.testing.assert_array_equal(exact, ref)


# ------------------------------------------------------------ slow sweeps --

@pytest.mark.slow
def test_mul_bit_identical_exhaustive_n10():
    """4^10 exhaustive cross-strategy identity at the LUT width cap."""
    a, b = _exhaustive_pairs(10)
    for spec in (MulSpec("truncated", 10, 5),
                 MulSpec("broken_array", 10, 4, 2),
                 MulSpec("mitchell", 10)):
        want = approx_mul(a, b, spec).astype(np.int64)
        np.testing.assert_array_equal(
            approx_mul(a, b, spec, fast=True).astype(np.int64), want)
        np.testing.assert_array_equal(
            lut_mul(a, b, spec).astype(np.int64), want)


@pytest.mark.slow
def test_closed_equals_compose_n12():
    """Closed form == compose at the compose cap (4^12 = 16.8M pairs)."""
    spec = MulSpec("truncated", 12, 6)
    closed = exact_mul_error_metrics(spec, method="closed")
    compose = exact_mul_error_metrics(spec, method="compose",
                                      cache_tables=False)
    for field in ("med", "mred", "nmed", "error_rate", "wce"):
        assert getattr(closed, field) == getattr(compose, field)

"""Tests for the compiled-LUT execution strategy (``repro.ax.lut``).

Acceptance (ISSUE 3): the ``lut`` strategy is bit-identical to the
reference form for ALL registered kinds across ALL valid (m, k) at N=8
(exhaustive) and N=16 (sampled); LUT tables round-trip through the
registry cache (same ``AdderSpec`` -> same table object); the
Monte-Carlo error sweep's lut path reproduces the reference reports
exactly.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.ax import (
    MAX_LUT_LSM_BITS,
    compile_lut,
    error_delta_table,
    get_adder,
    lut_supported,
    make_engine,
    registered_kinds,
)
from repro.ax.lut import abs_error_table, lut_index
from repro.core.specs import AdderSpec


def _valid_specs(kind: str, n_bits: int):
    """Every legal (m, k) for ``kind`` at width ``n_bits``."""
    entry = get_adder(kind)
    if entry.is_exact:
        return [AdderSpec(kind=kind, n_bits=n_bits)]
    specs = []
    for m in range(entry.min_lsm_bits, n_bits + 1):
        ks = (0,)
        if entry.const_section:
            ks = range(0, m - entry.const_margin + 1)
        for k in ks:
            specs.append(AdderSpec(kind=kind, n_bits=n_bits, lsm_bits=m,
                                   const_bits=k))
    return specs


def _exhaustive_pairs(n_bits):
    vals = np.arange(1 << n_bits, dtype=np.uint64)
    return np.repeat(vals, 1 << n_bits), np.tile(vals, 1 << n_bits)


@pytest.mark.parametrize("kind", registered_kinds())
def test_lut_bit_identical_exhaustive_n8_all_mk(kind):
    """lut == reference == fused on every 8-bit pair, for every legal
    (m, k) partition of every registered kind."""
    a, b = _exhaustive_pairs(8)
    for spec in _valid_specs(kind, 8):
        ref = make_engine(spec, backend="numpy").add_full(a, b)
        for strategy in ("fused", "lut"):
            got = make_engine(spec, backend="numpy",
                              strategy=strategy).add_full(a, b)
            np.testing.assert_array_equal(got, ref, err_msg=f"{spec} "
                                          f"{strategy}")


@pytest.mark.parametrize("kind", registered_kinds())
def test_lut_bit_identical_sampled_n16(kind):
    """lut == reference at N=16 on a random sample, for every legal
    (m, k) (the tables themselves are exhaustive in the low bits, the
    sample exercises the high-part add).  Tables wider than m=10 are
    covered by the single boundary case below — a full (m, k) sweep at
    m=11/12 would hold hundreds of MiB of cached tables."""
    rng = np.random.default_rng(11)
    a = rng.integers(0, 1 << 16, 50_000, dtype=np.uint64)
    b = rng.integers(0, 1 << 16, 50_000, dtype=np.uint64)
    for spec in _valid_specs(kind, 16):
        if not lut_supported(spec) or spec.lsm_bits > 10:
            continue
        ref = make_engine(spec, backend="numpy").add_full(a, b)
        got = make_engine(spec, backend="numpy",
                          strategy="lut").add_full(a, b)
        np.testing.assert_array_equal(got, ref, err_msg=str(spec))


def test_lut_widest_supported_table():
    """The MAX_LUT_LSM_BITS boundary compiles and stays bit-identical."""
    spec = AdderSpec(kind="haloc_axa", n_bits=16,
                     lsm_bits=MAX_LUT_LSM_BITS, const_bits=5)
    rng = np.random.default_rng(13)
    a = rng.integers(0, 1 << 16, 20_000, dtype=np.uint64)
    b = rng.integers(0, 1 << 16, 20_000, dtype=np.uint64)
    np.testing.assert_array_equal(
        make_engine(spec, backend="numpy", strategy="lut").add_full(a, b),
        make_engine(spec, backend="numpy").add_full(a, b))


def test_lut_jax_backend_matches_numpy():
    spec = AdderSpec(kind="haloc_axa", n_bits=16, lsm_bits=8, const_bits=4)
    a, b = _exhaustive_pairs(8)  # 16-bit pairs would be 4Gi; reuse 8-bit
    a, b = a * 257, b * 257      # spread over the 16-bit range
    a &= 0xFFFF
    b &= 0xFFFF
    want = np.asarray(make_engine(spec, backend="numpy",
                                  strategy="lut").add(a, b))
    got = np.asarray(make_engine(spec, backend="jax", strategy="lut").add(
        jnp.asarray(a.astype(np.int32)), jnp.asarray(b.astype(np.int32))))
    np.testing.assert_array_equal(got.astype(np.uint64), want)


@pytest.mark.parametrize("backend", ("pallas", "pallas_tpu"))
def test_lut_refused_on_pallas_backends(backend):
    """A VMEM table gather has no Mosaic lowering, so both Pallas
    backends refuse the lut strategy at the call instead of running a
    kernel the chip cannot compile; exact kinds have no table and keep
    the plain add."""
    spec = AdderSpec(kind="haloc_axa", n_bits=16, lsm_bits=8, const_bits=4)
    a = jnp.arange(8, dtype=jnp.int32)
    with pytest.raises(NotImplementedError, match="lut"):
        make_engine(spec, backend=backend, strategy="lut").add(a, a)
    exact = AdderSpec(kind="accurate", n_bits=16)
    if backend == "pallas":
        got = make_engine(exact, backend=backend, strategy="lut").add(a, a)
        np.testing.assert_array_equal(np.asarray(got), 2 * np.arange(8))


def test_lut_table_cache_round_trip():
    """Property: the registry cache returns the SAME table object for
    equal specs (and distinct objects for distinct specs)."""
    s1 = AdderSpec(kind="haloc_axa", n_bits=32, lsm_bits=10, const_bits=5)
    s2 = AdderSpec(kind="haloc_axa", n_bits=32, lsm_bits=10, const_bits=5)
    assert s1 is not s2
    assert compile_lut(s1) is compile_lut(s2)
    assert error_delta_table(s1) is error_delta_table(s2)
    assert abs_error_table(s1) is abs_error_table(s2)
    s3 = s1.replace(const_bits=4)
    assert compile_lut(s3) is not compile_lut(s1)
    # engines built for the same spec share the cache too
    e1 = make_engine(s1, backend="numpy", strategy="lut")
    e2 = make_engine(s2, backend="numpy", strategy="lut")
    assert e1 is e2
    # tables are immutable: nobody can corrupt the shared cache
    with pytest.raises(ValueError):
        compile_lut(s1)[0] = 0


def test_lut_packed_semantics():
    """The packed entry is low | cin << m, and read as an integer it is
    the approximate sum of the two low parts."""
    spec = AdderSpec(kind="haloc_axa", n_bits=16, lsm_bits=4, const_bits=2)
    table = compile_lut(spec)
    m = spec.lsm_bits
    assert table.dtype == np.uint16
    assert table.shape == (1 << (2 * m),)
    ref = make_engine(spec, backend="numpy")
    for a, bq in ((3, 5), (15, 15), (0, 0), (9, 12)):
        full = int(ref.add_full(np.uint64(a), np.uint64(bq)))
        assert int(table[(a << m) | bq]) == full  # high parts are zero


def test_lut_index_fast_path_matches_generic():
    """The little-endian uint64 view shortcut equals the mask/shift
    form (and non-contiguous inputs fall back to the generic path)."""
    spec = AdderSpec(kind="loa", n_bits=32, lsm_bits=10)
    rng = np.random.default_rng(5)
    a = rng.integers(0, 1 << 32, 10_000, dtype=np.uint64)
    b = rng.integers(0, 1 << 32, 10_000, dtype=np.uint64)
    m, low = spec.lsm_bits, (1 << spec.lsm_bits) - 1
    want = ((a & low) << m) | (b & low)
    np.testing.assert_array_equal(
        np.asarray(lut_index(a, b, spec), dtype=np.uint64), want)
    np.testing.assert_array_equal(
        np.asarray(lut_index(a[::2], b[::2], spec), dtype=np.uint64),
        want[::2])


def test_lut_add_broadcasts_like_reference():
    """Mismatched operand shapes (scalar plane, 2D-vs-1D) broadcast the
    same under the lut strategy as under the reference one (the 1-D
    fast index path must not swallow them)."""
    spec = AdderSpec(kind="haloc_axa", n_bits=16, lsm_bits=8, const_bits=4)
    ref = make_engine(spec, backend="numpy")
    lut = make_engine(spec, backend="numpy", strategy="lut")
    a = np.arange(16, dtype=np.uint64)
    b0 = np.asarray(np.uint64(37))                      # 0-d
    np.testing.assert_array_equal(lut.add(a, b0), ref.add(a, b0))
    b2 = np.arange(48, dtype=np.uint64).reshape(3, 16)  # 2-d vs 1-d
    np.testing.assert_array_equal(lut.add(a, b2), ref.add(a, b2))


def test_delta_table_is_full_sum_error():
    spec = AdderSpec(kind="oloca", n_bits=16, lsm_bits=6, const_bits=3)
    rng = np.random.default_rng(7)
    a = rng.integers(0, 1 << 16, 20_000, dtype=np.uint64)
    b = rng.integers(0, 1 << 16, 20_000, dtype=np.uint64)
    eng = make_engine(spec, backend="numpy")
    want = eng.add_full(a, b).astype(np.int64) - (a + b).astype(np.int64)
    got = error_delta_table(spec)[lut_index(a, b, spec)]
    np.testing.assert_array_equal(got.astype(np.int64), want)


def test_lut_unsupported_configurations():
    wide = AdderSpec(kind="loa", n_bits=32, lsm_bits=MAX_LUT_LSM_BITS + 1)
    assert not lut_supported(wide)
    with pytest.raises(ValueError, match="lsm_bits"):
        compile_lut(wide)
    with pytest.raises(ValueError, match="LUT"):
        make_engine(wide, strategy="lut")
    # exact kinds need no table: the strategy degrades to the plain add
    acc = AdderSpec(kind="accurate", n_bits=16)
    assert lut_supported(acc)
    with pytest.raises(ValueError, match="exact"):
        compile_lut(acc)
    eng = make_engine(acc, backend="numpy", strategy="lut")
    a = np.uint64(40_000)
    assert int(eng.add_full(a, a)) == 80_000


def test_unknown_strategy_rejected():
    with pytest.raises(ValueError, match="strategy"):
        make_engine("haloc_axa", strategy="warp")


def test_sweep_reports_match_per_spec_simulation():
    """simulate_error_metrics_sweep == per-spec simulate_error_metrics,
    for both strategies, to the last bit (shared operand stream)."""
    from repro.core.metrics import (simulate_error_metrics,
                                    simulate_error_metrics_sweep)
    from repro.core.specs import TABLE1_KINDS, paper_spec
    kinds = [k for k in TABLE1_KINDS if k != "accurate"]
    specs = [paper_spec(k) for k in kinds]
    want = {k: simulate_error_metrics(paper_spec(k), n_samples=100_000)
            for k in kinds}
    for strategy in ("reference", "lut"):
        got = simulate_error_metrics_sweep(specs, n_samples=100_000,
                                           strategy=strategy)
        for k, rep in zip(kinds, got):
            w = want[k]
            assert (rep.med, rep.mred, rep.error_rate, rep.wce) == \
                (w.med, w.mred, w.error_rate, w.wce), (strategy, k)


def test_sweep_rejects_mixed_widths():
    from repro.core.metrics import simulate_error_metrics_sweep
    with pytest.raises(ValueError, match="n_bits"):
        simulate_error_metrics_sweep(
            [AdderSpec(kind="loa", n_bits=16, lsm_bits=8),
             AdderSpec(kind="loa", n_bits=32, lsm_bits=10)],
            n_samples=1000)

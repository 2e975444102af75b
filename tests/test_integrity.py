"""repro.integrity: silent-corruption detection and online repair.

Acceptance (PR 10): every single-bit stuck-at corruption of every
cached N=8 adder-LUT entry is caught by the scrub digest check and the
repair restores bit-identical ``engine.add`` across backends; a
truncated or corrupted persistent-cache entry is never served; the
quick seeded detection campaign covers >= 95% of injected faults with
zero false positives; and everything is off (and costless) by default.
"""

import json
import os

import numpy as np
import pytest

import jax.numpy as jnp

from repro import obs
from repro.ax.engine import make_engine
from repro.ax.lut import _canonical, compile_lut, error_delta_table
from repro.ax.mul.specs import MulSpec
from repro.ax.registry import get_adder, registered_kinds
from repro.core.specs import AdderSpec
from repro.integrity import (AbftChecker, CanarySuite, LutScrubber,
                             PersistentCache, expected_add_outputs,
                             golden_entries, mac_error_budget, make_probe,
                             scrub_entries, table_digest,
                             verify_engine_tables, verify_entry)
from repro.integrity.digests import record_golden
from repro.integrity.store import activate, active_cache, deactivate
from repro.ioutil import (COMPILE_CACHE_ENV, atomic_replace_dir,
                          atomic_write_bytes, enable_compile_cache,
                          sha256_bytes, sha256_file)
from repro.numerics.fixed_point import FixedPointFormat
from repro.resilience.faults import FaultSpec
from repro.serving.clock import VirtualClock

SPEC = AdderSpec("haloc_axa", 16, lsm_bits=8, const_bits=4)
FMT16 = FixedPointFormat(16, 0)


@pytest.fixture()
def fresh_obs():
    obs.reset_all()
    obs.enable()
    yield
    obs.disable()
    obs.reset_all()


def _corrupt_in_place(table, idx, bitmask):
    table.flags.writeable = True
    table[idx] ^= type(table[idx])(bitmask)
    table.flags.writeable = False


# --------------------------------------------------------- ioutil --

def test_sha256_helpers_agree(tmp_path):
    payload = b"approximate adders\x00\xff" * 97
    p = tmp_path / "blob.bin"
    p.write_bytes(payload)
    assert sha256_file(str(p)) == sha256_bytes(payload)


def test_atomic_write_bytes_replaces_and_leaves_no_tmp(tmp_path):
    p = tmp_path / "entry.npy"
    atomic_write_bytes(str(p), b"first")
    atomic_write_bytes(str(p), b"second")
    assert p.read_bytes() == b"second"
    assert [f for f in os.listdir(tmp_path) if f.startswith(".tmp")] == []


def test_atomic_replace_dir(tmp_path):
    tmp = tmp_path / "stage"
    tmp.mkdir()
    (tmp / "a.txt").write_text("x")
    final = tmp_path / "published"
    final.mkdir()
    (final / "stale.txt").write_text("old")
    atomic_replace_dir(str(tmp), str(final))
    assert (final / "a.txt").read_text() == "x"
    assert not (final / "stale.txt").exists()
    assert not tmp.exists()


@pytest.fixture()
def restore_compile_cache_config():
    import jax
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    yield jax.config
    for k, v in saved.items():
        jax.config.update(k, v)


@pytest.mark.parametrize("from_env", (False, True))
def test_enable_compile_cache_placement(tmp_path, monkeypatch, from_env,
                                        restore_compile_cache_config):
    """The entry points' compile cache: JAX's own variable wins and the
    helper then sets no directory; otherwise a fixed directory under
    the given root.  Either way short kernel compiles are kept."""
    config = restore_compile_cache_config
    before = config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv(COMPILE_CACHE_ENV, str(tmp_path / "elsewhere"))
        assert enable_compile_cache(str(tmp_path)) == \
            str(tmp_path / "elsewhere")
        assert config.jax_compilation_cache_dir == before
    else:
        monkeypatch.delenv(COMPILE_CACHE_ENV, raising=False)
        want = str(tmp_path / ".jax_cache")
        assert enable_compile_cache(str(tmp_path)) == want
        assert enable_compile_cache(str(tmp_path)) == want
        assert config.jax_compilation_cache_dir == want
    assert config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_checkpointer_still_roundtrips_via_ioutil(tmp_path):
    """Satellite 1: the manifest extraction must leave checkpoint
    save/restore bit-identical (same digests, same integrity raise)."""
    from repro.checkpoint.checkpointer import Checkpointer
    ck = Checkpointer(str(tmp_path / "ckpt"))
    state = {"w": np.arange(12, dtype=np.int32).reshape(3, 4),
             "b": np.float64(1.5)}
    ck.save(0, state)
    got = ck.restore(like=state)
    np.testing.assert_array_equal(got["w"], state["w"])
    # flip one byte of a stored leaf -> restore must refuse
    leaf = next(p for p in
                sorted((tmp_path / "ckpt").rglob("*.npy")))
    raw = bytearray(leaf.read_bytes())
    raw[-1] ^= 0xFF
    leaf.write_bytes(bytes(raw))
    with pytest.raises(IOError, match="integrity"):
        ck.restore(like=state)


# ------------------------------------------------- golden registry --

def test_table_digest_sensitive_to_content_dtype_shape():
    a = np.arange(16, dtype=np.uint16)
    assert table_digest(a) == table_digest(a.copy())
    assert table_digest(a) != table_digest(a.astype(np.int32))
    assert table_digest(a) != table_digest(a.reshape(4, 4))
    b = a.copy()
    b[3] ^= 1
    assert table_digest(a) != table_digest(b)


def test_compile_registers_golden_and_verifies():
    table = compile_lut(SPEC)
    key = (_canonical(SPEC),)
    entries = [e for e in golden_entries("ax.lut.packed")
               if e.key == key]
    assert len(entries) == 1
    assert entries[0].table is table
    assert verify_entry(entries[0])


# ------------------------------------------------------- scrubbing --

def test_scrub_detects_and_repairs_in_place():
    table = compile_lut(SPEC)
    golden = table.copy()
    a = np.arange(1 << 12, dtype=np.uint64)
    b = a[::-1].copy()
    eng = make_engine(SPEC, backend="numpy", strategy="lut")
    want = np.asarray(eng.add(a, b)).copy()

    _corrupt_in_place(table, 5, 1 << 3)
    report = scrub_entries([e for e in golden_entries("ax.lut.packed")
                            if e.key == (_canonical(SPEC),)])
    assert not report.ok and report.repaired and not report.unrepaired
    np.testing.assert_array_equal(table, golden)
    # the engine gathers from the same array object: bit-identical again
    np.testing.assert_array_equal(np.asarray(eng.add(a, b)), want)


def test_scrubber_cadence_on_virtual_clock():
    clk = VirtualClock()
    s = LutScrubber(interval_s=10.0, clock=clk, cache="ax.lut.packed")
    compile_lut(SPEC)
    assert s.maybe_run() is None            # not due yet
    clk.advance(10.5)
    first = s.maybe_run()
    assert first is not None and first.ok
    assert s.maybe_run() is None            # cadence re-armed
    clk.advance(10.5)
    assert s.maybe_run() is not None
    assert s.runs == 2 and s.corruptions == 0


def test_scrubber_alarm_feed_trips_breaker_and_policy():
    from repro.serving.breaker import CircuitBreaker, OPEN

    class _Policy:
        def __init__(self):
            self.alarms = []

        def force_fallback(self):
            return True

        def on_integrity_alarm(self, report):
            self.alarms.append(report)
            return True

    table = compile_lut(SPEC)
    pol = _Policy()
    brk = CircuitBreaker()
    seen = []
    clk = VirtualClock()
    s = LutScrubber(interval_s=1.0, clock=clk, cache="ax.lut.packed",
                    breaker=brk, policy=pol, alarm=seen.append)
    _corrupt_in_place(table, 0, 1)
    clk.advance(1.5)
    report = s.maybe_run()
    assert not report.ok and report.repaired
    assert brk.state == OPEN and brk.trips == 1
    assert pol.alarms == [report] and seen == [report]


def test_unrepairable_corruption_stays_visible():
    """A corrupted table whose rebuild does NOT hash to the golden
    digest must not be silently 'repaired' with unverifiable data."""
    live = np.arange(8, dtype=np.uint16)
    entry_table = live.copy()
    record_golden("test.unrepairable", ("k",), entry_table,
                  lambda: np.zeros(8, dtype=np.uint16))  # bad rebuild
    entry = next(e for e in golden_entries("test.unrepairable"))
    _corrupt_in_place(entry_table, 2, 1)
    report = scrub_entries([entry])
    assert not report.ok and report.unrepaired and not report.repaired
    assert entry_table[2] == 3        # untouched: corruption left visible
    # un-corrupt before leaving: later full-registry scrubs (e.g. the
    # detection campaign's healthy pass) walk this entry too
    _corrupt_in_place(entry_table, 2, 1)
    assert verify_entry(entry)


def test_verify_engine_tables_repairs_before_serving():
    eng = make_engine(SPEC, backend="numpy", strategy="lut")
    table = compile_lut(SPEC)
    golden = table.copy()
    _corrupt_in_place(table, 17, 1 << 2)
    report = verify_engine_tables(SPEC)
    assert report.repaired
    np.testing.assert_array_equal(table, golden)


def test_make_engine_integrity_knob_repairs():
    table = compile_lut(SPEC)
    golden = table.copy()
    _corrupt_in_place(table, 9, 1 << 4)
    eng = make_engine(SPEC, backend="numpy", strategy="lut",
                      integrity=True)
    np.testing.assert_array_equal(table, golden)
    a, b = make_probe(SPEC.n_bits, n=64)
    np.testing.assert_array_equal(
        np.asarray(eng.add(a, b)) & np.uint64((1 << 16) - 1),
        expected_add_outputs(SPEC, a, b))


def test_exhaustive_n8_single_bit_stuckat_detection():
    """Satellite 3 acceptance: for EVERY non-exact registered kind at
    N=8, EVERY single-bit stuck-at corruption of EVERY cached LUT entry
    is caught by the digest check, and one repair pass restores
    bit-identical ``engine.add`` on every backend."""
    from repro.ax.lut import lut_supported

    a, b = make_probe(8, n=512, seed=3)
    mask8 = np.uint64(0xFF)
    for kind in registered_kinds():
        if get_adder(kind).is_exact:
            continue
        spec = AdderSpec(kind, 8, lsm_bits=4, const_bits=2)
        if not lut_supported(spec):
            continue
        table = compile_lut(spec)
        golden = table.copy()
        entry = next(e for e in golden_entries("ax.lut.packed")
                     if e.key == (_canonical(spec),))
        width = spec.lsm_bits + 1          # low sum | carry
        missed = 0
        for idx in range(table.size):
            for bit in range(width):
                for stuck in (0, 1):
                    clean = int(golden[idx])
                    want = (clean | (1 << bit)) if stuck else \
                        (clean & ~(1 << bit))
                    if want == clean:
                        continue           # unobservable: no corruption
                    table.flags.writeable = True
                    table[idx] = want
                    table.flags.writeable = False
                    if verify_entry(entry):
                        missed += 1
                    table.flags.writeable = True
                    table[idx] = golden[idx]
                    table.flags.writeable = False
        assert missed == 0, f"{kind}: {missed} corruptions escaped"

        # one full detect+repair cycle, then cross-backend bit-identity
        _corrupt_in_place(table, table.size // 2, 1 << (width - 1))
        report = scrub_entries([entry])
        assert report.repaired
        np.testing.assert_array_equal(table, golden)
        want = expected_add_outputs(spec, a, b)
        for backend in ("numpy", "jax"):
            eng = make_engine(spec, backend=backend, strategy="lut")
            if backend == "numpy":
                aa, bb = a, b
            else:
                aa = jnp.asarray(a.astype(np.uint32))
                bb = jnp.asarray(b.astype(np.uint32))
            got = np.asarray(eng.add(aa, bb))
            np.testing.assert_array_equal(
                got.astype(np.uint64) & mask8, want,
                err_msg=f"{kind}/{backend}")


# ---------------------------------------------------------- canary --

def test_canary_healthy_never_fails():
    for kind in ("haloc_axa", "loa", "eta"):
        for backend in ("numpy", "jax"):
            eng = make_engine(kind, backend=backend, strategy="lut")
            report = CanarySuite(eng, n=256).run_once(0.0)
            assert report.ok, f"{kind}/{backend}: {report}"


def test_canary_detects_output_bus_fault():
    fault = FaultSpec("stuck_at_1", bits=(13,))
    eng = make_engine("haloc_axa", backend="numpy", strategy="lut",
                      fault=fault)
    suite = CanarySuite(eng)
    report = suite.run_once(0.0)
    assert not report.ok and report.add_mismatches > 0
    assert suite.failures == 1


def test_canary_cadence_and_alarm():
    from repro.serving.breaker import CircuitBreaker, OPEN
    fault = FaultSpec("bit_flip", bits=(5, 21), rate=0.25)
    clk = VirtualClock()
    brk = CircuitBreaker()
    eng = make_engine("haloc_axa", backend="numpy", strategy="lut",
                      fault=fault)
    suite = CanarySuite(eng, interval_s=5.0, clock=clk, breaker=brk)
    assert suite.maybe_run() is None
    clk.advance(5.1)
    report = suite.maybe_run()
    assert report is not None and not report.ok
    assert brk.state == OPEN


def test_canary_covers_multiplier_products():
    eng = make_engine("haloc_axa", backend="numpy",
                      mul=MulSpec("broken_array", 8, 3, 1))
    suite = CanarySuite(eng, n=128)
    report = suite.run_once(0.0)
    assert report.ok and report.checked > 128 + 5    # add + mul probes


# ------------------------------------------------------------ abft --

def test_abft_budget_calibration_monotonic():
    b1 = mac_error_budget(SPEC, None, count=16, n_adds=1, n_products=0)
    b2 = mac_error_budget(SPEC, None, count=16, n_adds=4, n_products=0)
    assert 0 < b1 < b2
    exact = AdderSpec("accurate", 16)
    assert mac_error_budget(exact, None, 16, 4, 0) == 0.0


def test_abft_matmul_healthy_and_fault_detection():
    rng = np.random.default_rng(7)
    a = rng.integers(-128, 128, size=(24, 48), dtype=np.int64) \
        .astype(np.int8)
    b = rng.integers(-128, 128, size=(48, 32), dtype=np.int64) \
        .astype(np.int8)
    eng = make_engine("haloc_axa", backend="numpy")
    ck = AbftChecker(eng)
    block = (128, 128, 16)
    v = ck.matmul(a, b, block=block)
    assert v.ok and not v.flagged_cols and not v.flagged_rows

    out = np.array(eng.matmul(a, b, block=block), copy=True)
    out[:, 3] ^= 1 << 19                       # stuck bus bit, one col
    v2 = ck.verify_matmul(out, a, b, block=block)
    assert not v2.ok and 3 in v2.flagged_cols
    exact = a.astype(np.int64) @ b.astype(np.int64)
    np.testing.assert_array_equal(v2.out[:, 3].astype(np.int64),
                                  exact[:, 3])
    assert ck.checks == 2 and ck.flags == 1


def test_abft_matmul_healthy_with_approx_multiplier():
    rng = np.random.default_rng(9)
    a = rng.integers(-128, 128, size=(16, 64), dtype=np.int64) \
        .astype(np.int8)
    b = rng.integers(-128, 128, size=(64, 16), dtype=np.int64) \
        .astype(np.int8)
    eng = make_engine("haloc_axa", backend="numpy", mul="broken_array")
    v = AbftChecker(eng).matmul(a, b, block=(128, 128, 16))
    assert v.ok


def test_abft_conv2d_healthy_and_fault_detection():
    rng = np.random.default_rng(11)
    spec = AdderSpec("haloc_axa", 16, lsm_bits=8, const_bits=4)
    eng = make_engine(spec, fmt=FMT16, backend="numpy",
                      mul=MulSpec("broken_array", 8, 3, 1))
    kernel = ((1, 3, 1), (3, -5, 3), (1, 3, 1))
    q = rng.integers(-255, 256, size=(3, 24, 24)).astype(np.int32)
    ck = AbftChecker(eng)
    v = ck.conv2d(q, kernel, shift=2)
    assert v.ok

    out = np.array(eng.conv2d(q, kernel, shift=2), copy=True)
    out[1] |= 1 << 12                          # stuck bus bit, one image
    v2 = ck.verify_conv2d(out, q, kernel, shift=2)
    assert not v2.ok and v2.flagged_rows == (1,)
    # flagged image recomputed on the exact datapath
    p = np.pad(q[1].astype(np.int64), 1, mode="edge")
    acc = np.zeros((24, 24), dtype=np.int64)
    for r in range(3):
        for c in range(3):
            acc += kernel[r][c] * p[r:r + 24, c:c + 24]
    np.testing.assert_array_equal(v2.out[1], (acc + 2) >> 2)


# ---------------------------------------------- persistent store --

def test_persistent_cache_roundtrip(tmp_path):
    cache = PersistentCache(str(tmp_path))
    table = np.arange(64, dtype=np.uint16)
    cache.put("unit", ("spec", 1), table)
    got = cache.get("unit", ("spec", 1))
    np.testing.assert_array_equal(got, table)
    assert cache.hits == 1 and cache.corrupt == 0
    assert cache.get("unit", ("other", 2)) is None
    assert cache.misses == 1


def test_persistent_cache_never_serves_corruption(tmp_path):
    cache = PersistentCache(str(tmp_path))
    table = np.arange(256, dtype=np.uint16)
    cache.put("unit", "k", table)
    entry = next(p for p in tmp_path.iterdir() if p.suffix == ".npy")
    raw = bytearray(entry.read_bytes())
    raw[-3] ^= 0x40
    entry.write_bytes(bytes(raw))
    assert cache.get("unit", "k") is None      # detected, dropped
    assert cache.corrupt == 1
    assert not entry.exists()                  # corrupt entry deleted
    # and a rebuilt put serves again
    cache.put("unit", "k", table)
    np.testing.assert_array_equal(cache.get("unit", "k"), table)


def test_persistent_cache_never_serves_truncation(tmp_path):
    cache = PersistentCache(str(tmp_path))
    cache.put("unit", "k", np.arange(1024, dtype=np.int32))
    entry = next(p for p in tmp_path.iterdir() if p.suffix == ".npy")
    entry.write_bytes(entry.read_bytes()[:100])   # torn write
    assert cache.get("unit", "k") is None
    assert cache.corrupt == 1


def test_persistent_cache_version_salt_invalidates(tmp_path):
    a = PersistentCache(str(tmp_path), salt="v1")
    b = PersistentCache(str(tmp_path), salt="v2")
    a.put("unit", "k", np.ones(4))
    assert b.get("unit", "k") is None


def test_compile_lut_warm_starts_from_persistent_cache(tmp_path):
    spec = AdderSpec("loawa", 16, lsm_bits=6, const_bits=0)
    activate(str(tmp_path))
    try:
        compile_lut.cache_clear()
        cold = compile_lut(spec).copy()
        store = active_cache()
        assert store.misses >= 1
        compile_lut.cache_clear()           # "new process"
        warm = compile_lut(spec)
        assert store.hits >= 1
        np.testing.assert_array_equal(warm, cold)
        # warm-started tables still verify against the golden digest
        entry = next(e for e in golden_entries("ax.lut.packed")
                     if e.key == (_canonical(spec),))
        assert verify_entry(entry)
    finally:
        deactivate()
        compile_lut.cache_clear()


def test_corrupt_persistent_entry_falls_back_to_recompile(tmp_path):
    spec = AdderSpec("loa", 16, lsm_bits=6, const_bits=0)
    activate(str(tmp_path))
    try:
        compile_lut.cache_clear()
        cold = compile_lut(spec).copy()
        for p in tmp_path.iterdir():        # corrupt every entry
            if p.suffix == ".npy":
                raw = bytearray(p.read_bytes())
                raw[len(raw) // 2] ^= 0xFF
                p.write_bytes(bytes(raw))
        compile_lut.cache_clear()
        rebuilt = compile_lut(spec)
        np.testing.assert_array_equal(rebuilt, cold)
        assert active_cache().corrupt >= 1
    finally:
        deactivate()
        compile_lut.cache_clear()


def test_store_inactive_by_default(tmp_path, monkeypatch):
    import repro.integrity.store as store_mod
    monkeypatch.delenv(store_mod.CACHE_ENV, raising=False)
    deactivate()
    assert active_cache() is None
    assert store_mod.cache_get("x", "k") is None   # no-op, no raise


# ------------------------------------------- serving integration --

def test_scheduler_ticks_integrity_watchdogs():
    import repro.serving as sv
    table = compile_lut(SPEC)
    clk = sv.VirtualClock()
    ex = sv.SimExecutor(clk, pix_per_s=1e6)
    brk = sv.CircuitBreaker()
    scrubber = LutScrubber(interval_s=2.0, clock=clk,
                           cache="ax.lut.packed", breaker=brk)
    sched = sv.Scheduler(ex, clock=clk, breaker=brk,
                         integrity=scrubber)
    assert sched.integrity == (scrubber,)
    sched.pump()
    assert scrubber.runs == 0                  # not due yet
    _corrupt_in_place(table, 2, 1)
    clk.advance(2.5)
    sched.pump()
    assert scrubber.runs == 1 and scrubber.corruptions == 1
    assert brk.state == sv.OPEN                # alarm gated dispatch
    report = scrubber.last_report
    assert report.repaired                     # and repaired in place


def test_breaker_record_integrity_trips_and_degrades(fresh_obs):
    import repro.serving as sv
    from repro.imgproc.plan import PIPELINES, compile_pipeline
    from repro.resilience.degrade import DegradePolicy

    pipe = compile_pipeline(PIPELINES["pipe_blur_sharpen_down"],
                            kind="haloc_axa", backend="numpy")
    pol = DegradePolicy(pipe, min_samples=256)
    brk = sv.CircuitBreaker(policy=pol)
    brk.record_integrity(0.0)
    assert brk.state == sv.OPEN and brk.trips == 1
    assert pol.level == 1                      # stepped one Pareto rung
    # direct alarm path steps another rung
    assert pol.on_integrity_alarm(None)
    assert pol.level == 2


# ------------------------------------------------------- campaign --

def test_quick_detection_campaign_meets_acceptance():
    from repro.resilience.harness import detection_campaign
    records = detection_campaign(quick=True)
    assert records
    detected = sum(r["detected"] for r in records)
    cells = sum(r["cells"] for r in records)
    assert detected / cells >= 0.95
    assert all(r["false_positive_rate"] == 0.0 for r in records)
    assert all(np.isfinite(r["detection_latency_s"]) for r in records
               if r["detected"])
    assert all(json.dumps(r) for r in records)   # trajectory-ready


def test_detection_records_are_trajectory_keyed():
    from benchmarks.run import METRIC_FIELDS, record_key
    from repro.resilience.harness import detection_campaign
    records = detection_campaign(quick=True)
    keys = {record_key(r) for r in records}
    assert len(keys) == len(records)            # identity is unique
    for r in records:
        for metric in ("detected", "cells", "coverage",
                       "detection_latency_s", "false_positive_rate"):
            assert metric in METRIC_FIELDS

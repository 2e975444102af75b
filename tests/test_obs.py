"""repro.obs: spans, metrics, cache stats, and quality-drift telemetry.

Covers the three pillars plus their integration seams: spans read
back from a CPU profiler capture (nesting, stats, threads), histogram
percentile math, the named cache-stats facade over the package's
``lru_cache`` sites, the drift monitor (matched config stays quiet,
mis-budgeted config trips), the engine shadow-capture path, the
extended ``StreamResult`` latency summary, and — the contract the
whole design hangs on — that with telemetry off and no capture live
nothing records and shared no-op objects are returned.
"""

import json

import numpy as np
import pytest

from repro import obs
from repro.core.specs import AdderSpec
from repro.imgproc.corpus import (CorpusResult, StreamResult,
                                  format_table, run_streaming)


@pytest.fixture()
def fresh_obs():
    """Telemetry ON with clean state; always OFF and clean afterwards."""
    obs.reset_all()
    obs.enable()
    yield
    obs.disable()
    obs.reset_all()


# ------------------------------------------------------------- spans --

def test_span_nesting_order_and_parents(fresh_obs, capture):
    with capture() as cap:
        with obs.span("outer", label="a"):
            assert obs.current_span() == "outer"
            with obs.span("inner"):
                assert obs.current_stack() == ("outer", "inner")
    assert obs.current_stack() == ()
    outer, = cap.named("outer")
    inner, = cap.named("inner")
    # Nesting is in the profiler's clock: inner lies inside outer, on
    # the same thread.
    assert outer.start_ns <= inner.start_ns <= inner.end_ns <= outer.end_ns
    assert inner.thread == outer.thread
    assert outer.stats == {"label": "a"}
    assert inner.stats == {}


def test_span_set_attaches_args(fresh_obs, capture):
    with capture() as cap:
        with obs.span("s") as sp:
            sp.set(tiles=9)
    assert cap.named("s")[0].stats == {"tiles": 9}


def test_span_threads_get_disjoint_stacks(fresh_obs, capture):
    import threading
    seen = {}

    def worker():
        # A fresh thread starts with an empty stack even while the main
        # thread holds spans open (context-var isolation).
        seen["stack"] = obs.current_stack()
        with obs.span("worker-span"):
            seen["inner"] = obs.current_stack()

    with capture() as cap:
        with obs.span("main-span"):
            t = threading.Thread(target=worker)
            t.start()
            t.join()
    assert seen["stack"] == ()
    assert seen["inner"] == ("worker-span",)
    worker_ev, = cap.named("worker-span")
    main_ev, = cap.named("main-span")
    assert worker_ev.thread != main_ev.thread


def test_span_records_under_a_capture_with_telemetry_off(capture):
    obs.disable()
    with capture() as cap:
        assert obs.live()
        with obs.span("stream:dispatch", batch=3, shape=(4, 64)):
            assert obs.current_stack() == ("stream:dispatch",)
    assert not obs.live()
    ev, = cap.named("stream:dispatch")
    assert ev.stats == {"batch": 3, "shape": "(4, 64)"}
    assert ev.end_ns >= ev.start_ns


def test_span_off_records_nothing_and_is_the_shared_noop(capture):
    obs.disable()
    assert not obs.live()
    off = obs.span("decided-off", x=1)
    assert off is obs.span("other")
    # Whether a span records is decided when it is made: the no-op
    # records nothing even when entered under a capture.
    with capture() as cap:
        with off:
            assert obs.current_stack() == ()
    assert not cap.named("decided-off")


# ----------------------------------------------------------- metrics --

def test_histogram_percentiles_exact(fresh_obs):
    h = obs.histogram("lat")
    for v in range(1, 101):
        h.record(float(v))
    assert h.count == 100
    assert h.mean == pytest.approx(50.5)
    # numpy linear interpolation: p50 of 1..100 is 50.5.
    assert h.percentile(50) == pytest.approx(50.5)
    assert h.percentile(95) == pytest.approx(95.05)
    assert h.percentile(99) == pytest.approx(99.01)
    s = h.summary()
    assert s["min"] == 1.0 and s["max"] == 100.0
    assert s["p99"] == pytest.approx(99.01)


def test_counter_and_gauge_high_water(fresh_obs):
    c = obs.counter("pixels")
    c.inc(10)
    c.inc(5)
    g = obs.gauge("in_flight")
    g.inc()
    g.inc()
    g.dec()
    snap = obs.metrics_snapshot()
    assert snap["counters"]["pixels"] == 15
    assert snap["gauges"]["in_flight"] == {"value": 1, "high_water": 2}


def test_write_metrics_is_json_safe(fresh_obs, tmp_path):
    obs.histogram("empty")  # all-nan summary must serialize
    obs.counter("n").inc()
    path = tmp_path / "metrics.json"
    obs.write_metrics(str(path))
    doc = json.loads(path.read_text())
    assert doc["counters"]["n"] == 1
    assert doc["histograms"]["empty"]["p50"] is None
    assert "caches" in doc


# ------------------------------------------------------- cache stats --

def test_cache_stats_cover_engine_and_lut_sites():
    # Registration is import-time; pull in every instrumented module.
    import repro.ax.mul.lut  # noqa: F401
    import repro.core.hwcost  # noqa: F401
    import repro.imgproc.plan  # noqa: F401
    import repro.imgproc.tiles  # noqa: F401
    names = obs.cache_names()
    for expected in ("ax.engine", "ax.lut.packed", "ax.lut.delta",
                     "imgproc.plan.compiled", "imgproc.tiles.compiled",
                     "ax.mul.lut.product", "core.hwcost.toggle"):
        assert expected in names, expected


def test_cache_stats_count_hits_and_misses():
    from repro.ax import make_engine
    from repro.obs.caches import get_cached
    get_cached("ax.lut.packed").cache_clear()
    spec = AdderSpec("haloc_axa", n_bits=16, lsm_bits=6, const_bits=3)
    before = obs.cache_stats("ax.lut.packed")["ax.lut.packed"]
    eng = make_engine(spec, backend="numpy", strategy="lut")
    a = np.arange(64, dtype=np.uint64)
    eng.add(a, a)
    mid = obs.cache_stats("ax.lut.packed")["ax.lut.packed"]
    assert mid["misses"] > before["misses"]  # first build missed
    eng.add(a, a)
    after = obs.cache_stats("ax.lut.packed")["ax.lut.packed"]
    assert after["hits"] > mid["hits"]       # warm call hit
    assert after["size"] >= 1
    # Stats are pull-based and need no telemetry flag.
    assert not obs.enabled()


def test_format_cache_stats_renders():
    text = obs.format_cache_stats("ax.")
    assert "ax.lut.packed" in text
    assert "hits" in text


# ------------------------------------------------------------- drift --

SPEC = AdderSpec("haloc_axa", n_bits=16, lsm_bits=8, const_bits=4)


def _uniform_operands(n=20000, seed=3):
    rng = np.random.default_rng(seed)
    return (rng.integers(0, 1 << 16, n, dtype=np.uint64),
            rng.integers(0, 1 << 16, n, dtype=np.uint64))


def test_drift_matched_config_stays_quiet():
    mon = obs.DriftMonitor(SPEC)
    a, b = _uniform_operands()
    for i in range(0, a.size, 4096):
        mon.observe_operands("blur", a[i:i + 4096], b[i:i + 4096])
    st = mon.status("blur")
    assert st.n >= mon.min_samples
    # Uniform operands through the budgeted spec: ratio ~ 1.0, inside
    # the band.
    assert 0.9 < st.ratio < 1.1
    assert not st.tripped
    assert mon.ok() and mon.drifted() == ()


def test_drift_trips_on_mis_budgeted_config():
    # The monitor believes the pipeline runs haloc_axa (m=8, k=4) but
    # the datapath actually runs plain LOA at the same geometry — a
    # config mismatch the offline corpus PSNR would not surface until
    # quality already shipped wrong.
    mon = obs.DriftMonitor(SPEC)
    actual = AdderSpec("loa", n_bits=16, lsm_bits=8, const_bits=4)
    a, b = _uniform_operands(seed=5)
    mon.observe_operands("sharpen", a, b, spec=actual)
    st = mon.status("sharpen")
    assert st.tripped
    assert st.ratio > mon.band
    assert mon.drifted() == ("sharpen",)
    assert "DRIFT" in mon.report()


def test_drift_needs_min_samples():
    mon = obs.DriftMonitor(SPEC, min_samples=1024)
    mon.observe_errors("s", np.full(100, 1e6))  # huge error, tiny n
    assert not mon.status("s").tripped
    mon.observe_errors("s", np.full(1024, 1e6))
    assert mon.status("s").tripped


def test_drift_exact_kind_budget_is_zero():
    exact = AdderSpec("accurate", n_bits=16, lsm_bits=8)
    mon = obs.DriftMonitor(exact, min_samples=1)
    a, b = _uniform_operands(n=64)
    mon.observe_operands("s", a, b)
    st = mon.status("s")
    assert st.mean_abs == 0.0 and not st.tripped


def test_engine_capture_labels_stage_from_span(fresh_obs):
    from repro.ax import make_engine
    eng = make_engine(SPEC, backend="numpy", strategy="reference")
    a, b = _uniform_operands(n=4096, seed=9)
    with obs.installed(obs.DriftMonitor(SPEC, min_samples=1)) as mon:
        with obs.span("stage:gaussian_blur"):
            eng.add(a, b)
        eng.add(a, b)  # outside any stage span
    stages = {st.stage for st in mon.statuses()}
    assert stages == {"gaussian_blur", "unlabeled"}
    assert mon.status("gaussian_blur").n > 0


def test_engine_capture_off_when_disabled():
    from repro.ax import make_engine
    obs.disable()
    eng = make_engine(SPEC, backend="numpy", strategy="reference")
    a, b = _uniform_operands(n=256)
    with obs.installed(obs.DriftMonitor(SPEC, min_samples=1)) as mon:
        eng.add(a, b)
    assert mon.statuses() == ()


def test_numpy_pipeline_capture_end_to_end(fresh_obs):
    # The intended production pattern: a shadow crop through the numpy
    # backend reports per-stage drift without touching the jitted path.
    from repro.imgproc import run_pipeline, synthetic_batch
    batch = synthetic_batch(1, 32, seed=2)
    with obs.installed(obs.DriftMonitor(SPEC, min_samples=64)) as mon:
        run_pipeline(("gaussian_blur", "sharpen"), batch,
                     kind="haloc_axa", backend="numpy")
    stages = {st.stage for st in mon.statuses()}
    assert "gaussian_blur" in stages and "sharpen" in stages
    assert mon.ok(), mon.report()


# ----------------------------------------------------- disabled = off --

def test_disabled_span_is_shared_noop():
    obs.disable()
    s1, s2 = obs.span("a"), obs.span("b", x=1)
    assert s1 is s2  # ONE shared object, no allocation per call
    s1.set(tiles=3)  # dropped
    with obs.span("not-recorded"):
        assert obs.current_stack() == ()  # stack untouched


def test_disabled_instruments_are_shared_noop():
    obs.disable()
    c = obs.counter("x")
    assert c is obs.gauge("y") is obs.histogram("z")
    c.inc(100)
    c.record(1.0)
    c.set(5)
    assert np.isnan(c.percentile(50))
    snap = obs.metrics_snapshot()
    assert "x" not in snap["counters"]
    assert "z" not in snap["histograms"]


def test_telemetry_scope_restores_flag():
    obs.disable()
    with obs.telemetry(True):
        assert obs.enabled()
        with obs.telemetry(False):
            assert not obs.enabled()
        assert obs.enabled()
    assert not obs.enabled()


# --------------------------------------------- streaming integration --

def test_stream_result_latency_percentiles():
    lat = tuple(float(v) for v in range(1, 11))
    r = StreamResult(outputs=[], seconds=1.0, pixels=10 ** 6,
                     batch_seconds=lat)
    assert r.p50_s == pytest.approx(5.5)
    assert r.p95_s == pytest.approx(9.55)
    assert r.p99_s == pytest.approx(9.91)
    # Back-compat: results without the field summarize as nan.
    legacy = StreamResult(outputs=[], seconds=1.0, pixels=1)
    assert np.isnan(legacy.p50_s)


def test_run_streaming_records_latencies_without_telemetry():
    obs.disable()
    batches = [np.zeros((1, 8, 8), np.uint8) for _ in range(5)]
    r = run_streaming(lambda b: b, batches, depth=2)
    assert len(r.batch_seconds) == 5
    assert all(t >= 0 for t in r.batch_seconds)
    assert r.p95_s >= r.p50_s


def test_run_streaming_metrics_when_enabled(fresh_obs, capture):
    batches = [np.zeros((1, 8, 8), np.uint8) for _ in range(4)]
    with capture() as cap:
        run_streaming(lambda b: b, batches, depth=2)
    snap = obs.metrics_snapshot()
    assert snap["counters"]["stream.batches"] == 4
    assert snap["counters"]["stream.pixels"] == 4 * 64
    assert snap["histograms"]["stream.batch_seconds"]["count"] == 4
    assert snap["gauges"]["stream.batches_in_flight"]["value"] == 0
    assert snap["gauges"]["stream.batches_in_flight"]["high_water"] == 2
    for name in ("stream:dispatch", "stream:wait", "stream:fetch"):
        assert sorted(e.stats["batch"] for e in cap.named(name)) == \
            [0, 1, 2, 3]


def test_run_streaming_spans_under_capture_without_telemetry(capture):
    import jax.numpy as jnp
    obs.disable()
    batches = [np.full((1, 8, 8), i, np.uint8) for i in range(3)]
    with capture() as cap:
        res = run_streaming(lambda b: jnp.asarray(b) + 1, batches, depth=2)
    for i, out in enumerate(res.outputs):
        np.testing.assert_array_equal(out, batches[i] + 1)
    # The drain is split: the wait for the device, then the copy-out.
    waits = {e.stats["batch"]: e for e in cap.named("stream:wait")}
    fetches = {e.stats["batch"]: e for e in cap.named("stream:fetch")}
    dispatches = {e.stats["batch"]: e for e in cap.named("stream:dispatch")}
    assert sorted(waits) == sorted(fetches) == sorted(dispatches) == [0, 1, 2]
    for i in range(3):
        assert dispatches[i].end_ns <= waits[i].start_ns
        assert waits[i].end_ns <= fetches[i].start_ns
    assert not obs.metrics_snapshot()["counters"]


# ------------------------------------------------- satellite behavior --

def test_timeit_result_is_float_compatible():
    from benchmarks.timing import TimingResult, timeit_jax
    t = timeit_jax(lambda: np.arange(8), reps=2, rounds=3)
    assert isinstance(t, float)
    assert float(t) == min(t.rounds)
    assert len(t.rounds) == 3
    assert t.spread == pytest.approx(max(t.rounds) - min(t.rounds))
    assert t * 1e3 >= 0.0  # arithmetic stays float
    r = TimingResult((2.0, 1.0, 4.0))
    assert float(r) == 1.0 and r.mean == pytest.approx(7.0 / 3)
    assert r.spread == 3.0 and r.jitter == 3.0
    with pytest.raises(ValueError):
        TimingResult(())


def _cell(psnr, workload="w"):
    return CorpusResult(kind="k", workload=workload, psnr=psnr,
                        ssim=0.5, band="good", mpix_per_s=1.0,
                        seconds=1.0)


def test_format_table_renders_inf_and_high_psnr():
    table = format_table([_cell(float("inf"), "a"), _cell(123.4, "b"),
                          _cell(42.0, "c")])
    assert "inf/0.500" in table
    assert ">=99/0.500" in table      # real >=99 values are not clamped
    assert "99.0/0.500" not in table  # the old silent clamp is gone
    assert "42.0/0.500" in table


def test_trajectory_key_ignores_provenance_and_new_metrics():
    from benchmarks.run import merge_records, record_key
    committed = {"op": "mega/stream", "kind": "haloc_axa", "depth": 2,
                 "mpix_per_s": 100.0}
    stamped = {"op": "mega/stream", "kind": "haloc_axa", "depth": 2,
               "mpix_per_s": 120.0, "p95_ms": 9.0, "jitter_pct": 1.0,
               "host_platform": "Linux-x", "jax_version": "0.0.0",
               "device_kind": "cpu"}
    assert record_key(committed) == record_key(stamped)
    merged = merge_records([committed], [stamped])
    assert merged == [stamped]  # updated in place, not forked

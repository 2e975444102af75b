"""repro.serving: deadline-aware scheduling over the compiled stack.

Acceptance (PR 9), all on a virtual clock — deterministic, zero wall
sleeps: under seeded >= 2x-capacity overload the scheduler sheds and
rejects instead of queueing unboundedly, p99 of accepted requests stays
within 3x the uncontended p99, no request is EVER dispatched after its
deadline expired, and the circuit breaker demonstrably trips to a
cheaper Pareto rung (``DegradePolicy.force_fallback``) and recovers
through its half-open probe.
"""

import dataclasses

import numpy as np
import pytest

from repro import obs
from repro import serving as sv
from repro.runtime.straggler import StragglerConfig, StragglerMonitor


@pytest.fixture()
def fresh_obs():
    obs.reset_all()
    obs.enable()
    yield
    obs.disable()
    obs.reset_all()


def _img(size=32, fill=7):
    return np.full((size, size), fill, np.uint8)


def _sim(pix_per_s=1e6, **kw):
    clk = sv.VirtualClock()
    ex = sv.SimExecutor(clk, pix_per_s=pix_per_s, **kw)
    est = sv.CostEstimator(pix_per_s=pix_per_s)
    return clk, ex, est


# -------------------------------------------------------------- clock --

def test_virtual_clock_basics():
    clk = sv.VirtualClock()
    assert clk.now() == 0.0
    clk.sleep(0.5)
    assert clk.now() == 0.5
    clk.advance_to(0.25)                 # never rewinds
    assert clk.now() == 0.5
    clk.advance_to(1.5)
    assert clk.now() == 1.5
    with pytest.raises(ValueError, match="cannot advance"):
        clk.advance(-0.1)


def test_virtual_clock_positive_advance_always_moves():
    """Regression: sleeping a sub-ulp residue (the float leftovers of a
    breaker cooldown) must still advance time, or a discrete-event loop
    that sleeps ``retry_after`` freezes forever."""
    clk = sv.VirtualClock(start=0.27486669760536514)
    t0 = clk.now()
    clk.sleep(1.3877787807814457e-17)    # absorbed by plain float add
    assert clk.now() > t0
    clk.sleep(0.0)                       # a zero sleep is still a no-op
    assert clk.now() == pytest.approx(t0, abs=1e-12)


def test_wall_clock_is_monotone():
    clk = sv.WallClock()
    a = clk.now()
    clk.sleep(0.0)
    assert clk.now() >= a


# ---------------------------------------------------------- estimator --

def test_estimator_ewma_and_validation():
    est = sv.CostEstimator(pix_per_s=1e6, overhead_s=0.001)
    assert est.estimate(1000) == pytest.approx(0.002)
    est.observe(1000, 0.0005)            # 2e6 pix/s: replaces the prior
    assert est.pix_per_s == pytest.approx(2e6)
    est.observe(1000, 0.001)             # 1e6 pix/s folds in via EWMA
    assert 1e6 < est.pix_per_s < 2e6
    n = est.observations
    est.observe(0, 1.0)                  # degenerate: ignored
    est.observe(100, 0.0)
    assert est.observations == n
    with pytest.raises(ValueError, match="pix_per_s"):
        sv.CostEstimator(pix_per_s=0)
    with pytest.raises(ValueError, match="overhead_s"):
        sv.CostEstimator(overhead_s=-1)
    with pytest.raises(ValueError, match="alpha"):
        sv.CostEstimator(alpha=0.0)


def test_estimator_calibrate_from_sim_executor():
    clk = sv.VirtualClock()
    ex = sv.SimExecutor(clk, pix_per_s=2e6)
    est = sv.CostEstimator(pix_per_s=123.0)
    measured = est.calibrate(ex, _img(32), "pipe_blur_sharpen_down", clk)
    assert measured == pytest.approx(2e6)
    assert est.estimate(2e6) == pytest.approx(1.0)


# ------------------------------------------- admission / backpressure --

def test_queue_full_rejection_is_typed():
    q = sv.AdmissionQueue(sv.AdmissionConfig(max_depth=2, preempt=False))
    assert q.offer(sv.Request(image=_img())) == (None, None)
    assert q.offer(sv.Request(image=_img())) == (None, None)
    rej, evicted = q.offer(sv.Request(image=_img()))
    assert evicted is None
    assert isinstance(rej, sv.Rejected) and not rej.ok
    assert rej.reason == "queue_full" and rej.depth == 2
    assert len(q) == 2                   # refusal never grows the queue


def test_backlog_rejection_is_typed():
    est = sv.CostEstimator(pix_per_s=1e6)   # 32x32 -> ~1 ms each
    q = sv.AdmissionQueue(
        sv.AdmissionConfig(max_depth=64, max_backlog_s=0.0025), est)
    assert q.offer(sv.Request(image=_img()))[0] is None
    assert q.offer(sv.Request(image=_img()))[0] is None
    rej, _ = q.offer(sv.Request(image=_img()))
    assert rej is not None and rej.reason == "backlog"
    assert rej.backlog_s == pytest.approx(2 * 1024 / 1e6)


def test_priority_preemption_evicts_lowest():
    q = sv.AdmissionQueue(sv.AdmissionConfig(max_depth=2))
    lo = sv.Request(image=_img(), priority=0)
    mid = sv.Request(image=_img(), priority=1)
    q.offer(lo)
    q.offer(mid)
    hi = sv.Request(image=_img(), priority=2)
    rej, evicted = q.offer(hi)
    assert rej is None and evicted is lo    # lowest priority loses
    assert len(q) == 2
    # An equal-priority arrival cannot preempt: typed rejection.
    rej, evicted = q.offer(sv.Request(image=_img(), priority=1))
    assert rej is not None and evicted is None


def test_preemption_undone_when_backlog_still_refuses():
    est = sv.CostEstimator(pix_per_s=1e6)
    q = sv.AdmissionQueue(
        sv.AdmissionConfig(max_depth=1, max_backlog_s=0.0005), est)
    small = sv.Request(image=_img(8), priority=0)        # 64 px
    assert q.offer(small)[0] is None
    big = sv.Request(image=_img(64), priority=5)         # 4096 px > cap
    rej, evicted = q.offer(big)
    assert rej is not None and rej.reason == "backlog"
    assert evicted is None
    assert q.requests(small.bucket) == (small,)          # victim restored


def test_take_orders_priority_then_fifo():
    q = sv.AdmissionQueue(sv.AdmissionConfig(max_depth=8))
    reqs = [sv.Request(image=_img(), priority=p) for p in (0, 2, 1, 2)]
    for r in reqs:
        q.offer(r)
    chosen = q.take(reqs[0].bucket, 3)
    # Top-3 by priority (2, 2, 1), dispatched in admission order.
    assert chosen == (reqs[1], reqs[2], reqs[3])
    assert len(q) == 1


# ------------------------------------------------------------ batcher --

def _queued(requests, est=None):
    q = sv.AdmissionQueue(sv.AdmissionConfig(max_depth=64), est)
    for r in requests:
        assert q.offer(r)[0] is None
    return q


def test_batcher_dispatches_on_fill():
    est = sv.CostEstimator(pix_per_s=1e6)
    b = sv.Batcher(sv.BatcherConfig(max_batch=3, max_wait_s=1.0), est)
    reqs = [dataclasses.replace(sv.Request(image=_img()), arrival=0.0)
            for _ in range(3)]
    q = _queued(reqs, est)
    assert b.due(q, reqs[0].bucket, now=0.0)      # full: no waiting
    batches = b.collect(q, now=0.0)
    assert len(batches) == 1 and len(batches[0]) == 3
    assert batches[0].pipeline == "pipe_blur_sharpen_down"
    assert len(q) == 0


def test_batcher_dispatches_on_max_wait():
    est = sv.CostEstimator(pix_per_s=1e6)
    b = sv.Batcher(sv.BatcherConfig(max_batch=4, max_wait_s=0.010), est)
    req = dataclasses.replace(sv.Request(image=_img()), arrival=0.0)
    q = _queued([req], est)
    assert not b.due(q, req.bucket, now=0.004)    # light load: wait
    assert b.due(q, req.bucket, now=0.010)        # latency floor hit


def test_batcher_dispatches_on_deadline_margin():
    est = sv.CostEstimator(pix_per_s=1e6)         # ~1 ms service
    b = sv.Batcher(sv.BatcherConfig(max_batch=4, max_wait_s=10.0,
                                    safety=2.0), est)
    req = dataclasses.replace(
        sv.Request(image=_img(), deadline=0.0035), arrival=0.0)
    q = _queued([req], est)
    assert not b.due(q, req.bucket, now=0.0005)   # slack still covers
    assert b.due(q, req.bucket, now=0.002)        # slack < est * safety


def test_batcher_sheds_expired_and_doomed():
    est = sv.CostEstimator(pix_per_s=1e6)
    b = sv.Batcher(sv.BatcherConfig(max_batch=4), est)
    expired = dataclasses.replace(
        sv.Request(image=_img(), deadline=0.5), arrival=0.0)
    doomed = dataclasses.replace(
        sv.Request(image=_img(64), deadline=1.001), arrival=0.0)
    healthy = dataclasses.replace(
        sv.Request(image=_img(), deadline=5.0), arrival=0.0)
    q = _queued([expired, doomed, healthy], est)
    sheds = b.shed(q, now=1.0)
    assert {(s.rid, s.reason) for s in sheds} == \
        {(expired.rid, "expired"), (doomed.rid, "doomed")}
    assert len(q) == 1 and q.oldest(healthy.bucket) is healthy


# ---------------------------------------------------------- scheduler --

def test_scheduler_completes_and_routes_outputs():
    clk, ex, est = _sim()
    sched = sv.Scheduler(ex, clock=clk, estimator=est,
                         batching=sv.BatcherConfig(max_batch=2))
    reqs = [sv.Request(image=_img(fill=i)) for i in range(5)]
    for r in reqs:
        assert sched.submit(r) is None
    sched.drain()
    done = {o.rid: o for o in sched.outcomes}
    assert len(done) == 5
    for r in reqs:
        out = done[r.rid]
        assert isinstance(out, sv.Completed) and out.ok
        np.testing.assert_array_equal(out.output, r.image)  # echo routing
        assert out.attempts == 1 and not out.late
        assert out.finished >= out.started >= out.request.arrival
    assert len(sched.queue) == 0


def test_expired_request_is_shed_not_dispatched():
    clk, ex, est = _sim()
    sched = sv.Scheduler(ex, clock=clk, estimator=est)
    req = sv.Request(image=_img(), deadline=clk.now() + 0.01)
    sched.submit(req)
    clk.advance(0.02)                    # deadline passes in the queue
    out = sched.drain()
    assert [type(o) for o in out] == [sv.Shed]
    assert out[0].reason == "expired" and out[0].rid == req.rid
    assert ex.calls == 0                 # NEVER ran


def test_expired_mid_batch_is_shed_before_the_attempt():
    """The no-doomed-work guarantee inside ``_run_batch``: expiry is
    re-checked before EVERY attempt, so a request whose deadline passed
    during backoff never reaches the executor again."""
    clk, ex, est = _sim()
    # Every attempt fails and burns 50 ms of backoff; the deadline
    # (40 ms) expires during the FIRST backoff window.
    ex.fail_first = 10 ** 6
    sched = sv.Scheduler(
        ex, clock=clk, estimator=est,
        config=sv.SchedulerConfig(max_retries=3, backoff_s=0.05),
        batching=sv.BatcherConfig(max_batch=2))
    req = sv.Request(image=_img(), deadline=clk.now() + 0.04)
    sched.submit(req)
    out = sched.drain()
    assert [type(o) for o in out] == [sv.Shed]
    assert out[0].reason == "expired"
    assert ex.calls == 1                 # first attempt only


def test_retry_with_backoff_then_success():
    clk, ex, est = _sim(fail_first=1)
    sched = sv.Scheduler(ex, clock=clk, estimator=est,
                         config=sv.SchedulerConfig(max_retries=2,
                                                   backoff_s=0.001))
    req = sv.Request(image=_img())
    sched.submit(req)
    out = sched.drain()
    assert [type(o) for o in out] == [sv.Completed]
    assert out[0].attempts == 2
    assert ex.calls == 2


def test_poisoned_request_isolated_neighbors_survive():
    """One poisoned request fails ALONE: after batch retries exhaust,
    the batch splits and every healthy neighbor still completes."""
    poison = _img(fill=255)
    clk, ex, est = _sim(
        fail_when=lambda imgs: bool((imgs == 255).all(axis=(1, 2)).any()))
    sched = sv.Scheduler(ex, clock=clk, estimator=est,
                         config=sv.SchedulerConfig(max_retries=1,
                                                   backoff_s=0.0),
                         batching=sv.BatcherConfig(max_batch=3))
    good = [sv.Request(image=_img(fill=i)) for i in (1, 2)]
    bad = sv.Request(image=poison)
    for r in (good[0], bad, good[1]):
        sched.submit(r)
    sched.drain()
    done = {o.rid: o for o in sched.outcomes}
    assert isinstance(done[bad.rid], sv.Failed)
    assert done[bad.rid].attempts >= 3   # batch tries + isolated try
    for r in good:
        assert isinstance(done[r.rid], sv.Completed)
        np.testing.assert_array_equal(done[r.rid].output, r.image)


def test_timeout_verdict_routes_through_straggler_late():
    """A batch whose service time blows its estimated-service timeout
    is flagged through the repo-wide ``StragglerMonitor.late``."""
    clk = sv.VirtualClock()
    ex = sv.SimExecutor(clk, pix_per_s=1e4)        # 100x slower than est
    est = sv.CostEstimator(pix_per_s=1e6)
    mon = StragglerMonitor(StragglerConfig(min_samples=1 << 30))
    sched = sv.Scheduler(ex, clock=clk, estimator=est, straggler=mon,
                         config=sv.SchedulerConfig(timeout_factor=4.0))
    sched.submit(sv.Request(image=_img()))
    out = sched.drain()
    assert isinstance(out[0], sv.Completed)
    assert out[0].late                    # late but served, not dropped
    assert len(mon.times) == 1            # verdict recorded in the monitor


# ------------------------------------------------------------ breaker --

class _FakeLadder:
    """Duck-typed DegradePolicy: records forced fallbacks."""

    def __init__(self, rungs=3):
        self.level = 0
        self.ladder = tuple(range(rungs))

    @property
    def exhausted(self):
        return self.level >= len(self.ladder)

    def force_fallback(self):
        if self.exhausted:
            return False
        self.level += 1
        return True


def test_breaker_state_machine():
    br = sv.CircuitBreaker(sv.BreakerConfig(failure_threshold=2,
                                            cooldown_s=1.0,
                                            probe_successes=2))
    assert br.allow(0.0) and br.state == sv.CLOSED
    br.record_failure(0.0)
    assert br.state == sv.CLOSED          # one failure is not a trend
    br.record_failure(0.1)
    assert br.state == sv.OPEN and br.trips == 1
    assert not br.allow(0.5)              # cooling down
    assert br.retry_after(0.5) == pytest.approx(0.6)
    assert br.allow(1.2) and br.state == sv.HALF_OPEN and br.probing
    br.record_success(1.3)
    assert br.state == sv.HALF_OPEN       # needs probe_successes=2
    br.record_success(1.4)
    assert br.state == sv.CLOSED and not br.probing
    br.record_drift(2.0)                  # drift alarm: immediate trip
    assert br.state == sv.OPEN and br.trips == 2


def test_failed_probe_reopens_and_degrades_again():
    pol = _FakeLadder()
    br = sv.CircuitBreaker(sv.BreakerConfig(failure_threshold=1,
                                            cooldown_s=0.5), policy=pol)
    br.record_failure(0.0)
    assert pol.level == 1
    assert br.allow(0.6)                  # half-open probe window
    br.record_failure(0.7)                # probe failed
    assert br.state == sv.OPEN and br.trips == 2 and pol.level == 2
    assert not br.allow(0.8)


def test_breaker_trips_degrades_and_recovers_in_scheduler():
    """End-to-end trip/recovery on the scheduler: consecutive executor
    failures open the breaker (stepping the attached ladder), survivors
    are requeued — not failed — and after the cooldown a half-open
    probe closes the breaker and everything completes."""
    pol = _FakeLadder()
    clk, ex, est = _sim(fail_first=2)
    br = sv.CircuitBreaker(sv.BreakerConfig(failure_threshold=2,
                                            cooldown_s=0.01), policy=pol)
    sched = sv.Scheduler(ex, clock=clk, estimator=est, breaker=br,
                         config=sv.SchedulerConfig(max_retries=2,
                                                   backoff_s=0.001),
                         batching=sv.BatcherConfig(max_batch=4))
    reqs = [sv.Request(image=_img(fill=i)) for i in range(8)]
    for r in reqs:
        sched.submit(r)
    sched.drain()
    done = {o.rid: o for o in sched.outcomes}
    assert all(isinstance(done[r.rid], sv.Completed) for r in reqs)
    assert br.trips == 1 and br.state == sv.CLOSED
    assert pol.level == 1                 # one rung per trip
    assert ex.failures == 2


def test_breaker_steps_real_pareto_ladder():
    """Acceptance: a breaker trip lands the attached DegradePolicy on
    the next-cheapest rung of the REAL exact Pareto ladder (recompiled
    without the fault), and the half-open probe recovers."""
    from repro.imgproc.plan import PIPELINES, compile_pipeline
    from repro.resilience.degrade import DegradePolicy
    from repro.resilience.faults import FaultSpec
    pipe = compile_pipeline(PIPELINES["pipe_blur_sharpen_down"],
                            kind="haloc_axa", backend="numpy",
                            fault=FaultSpec("stuck_at_1", bits=(11,)))
    pol = DegradePolicy(pipe, min_samples=512)
    base_spec = pipe.engine.spec
    clk, ex, est = _sim(fail_first=1)
    br = sv.CircuitBreaker(sv.BreakerConfig(failure_threshold=1,
                                            cooldown_s=0.01), policy=pol)
    sched = sv.Scheduler(ex, clock=clk, estimator=est, breaker=br,
                         config=sv.SchedulerConfig(max_retries=1,
                                                   backoff_s=0.001))
    for i in range(3):
        sched.submit(sv.Request(image=_img(fill=i)))
    sched.drain()
    assert br.trips >= 1 and br.state == sv.CLOSED
    assert pol.level >= 1
    assert pol.pipe.engine.spec == pol.ladder[pol.level - 1]
    assert pol.pipe.engine.spec != base_spec
    assert pol.pipe.engine.fault is None  # fallback compiles healthy
    assert all(isinstance(o, sv.Completed) for o in sched.outcomes)


# --------------------------------------------- overload (acceptance) --

def _traffic_cell(rate_rps, n, seed, depth=64,
                  backlog_s=float("inf")):
    clk, ex, est = _sim()
    sched = sv.Scheduler(
        ex, clock=clk, estimator=est,
        admission=sv.AdmissionConfig(max_depth=depth,
                                     max_backlog_s=backlog_s),
        batching=sv.BatcherConfig(max_batch=4, max_wait_s=0.002))
    mix = sv.TrafficMix("cell", rate_rps=rate_rps, sizes=(32, 64),
                        size_weights=(0.8, 0.2), deadline_s=0.05)
    rep = sv.run_traffic(sched, sv.make_arrivals(mix, n=n, seed=seed),
                         mix.name)
    return rep, sched, ex


def test_overload_sheds_and_bounds_latency():
    """THE acceptance scenario.  Capacity of the simulated executor is
    ~610 req/s for this mix; 1200 req/s is ~2x overload."""
    base, _, _ = _traffic_cell(100.0, n=80, seed=3)
    assert len(base.completed) == base.offered == 80
    assert base.deadline_misses == 0

    over, sched, ex = _traffic_cell(1200.0, n=400, seed=4,
                                    depth=12, backlog_s=0.010)
    # Typed load shedding, not unbounded queueing: both mechanisms fire.
    assert len(over.rejected) > 0 and len(over.shed) > 0
    assert len(over.completed) > 0
    assert len(sched.queue) == 0
    # Every submitted request got exactly one outcome.
    assert over.offered == 400
    # Accepted latency stays bounded: within 3x the uncontended p99.
    assert over.p99_s <= 3.0 * base.p99_s
    # No request was EVER dispatched after its deadline expired.
    assert all(c.started < c.request.deadline for c in over.completed)
    # Goodput is real: the overloaded cell completes more pixels/s.
    assert over.goodput_mpix_per_s > base.goodput_mpix_per_s


def test_overload_replays_bit_identically():
    a, _, _ = _traffic_cell(1200.0, n=200, seed=11, depth=12,
                            backlog_s=0.010)
    b, _, _ = _traffic_cell(1200.0, n=200, seed=11, depth=12,
                            backlog_s=0.010)
    assert [type(o).__name__ for o in a.outcomes] == \
        [type(o).__name__ for o in b.outcomes]
    assert a.seconds == b.seconds
    assert a.p99_s == b.p99_s or (np.isnan(a.p99_s) and np.isnan(b.p99_s))
    assert a.record(load_x=2.0) == b.record(load_x=2.0)


def test_priority_survives_overload():
    """Under a full queue, high-priority arrivals preempt low-priority
    queued work (typed ``Shed(reason="preempted")``), so importance is
    what overload sacrifices last."""
    clk, ex, est = _sim()
    sched = sv.Scheduler(
        ex, clock=clk, estimator=est,
        admission=sv.AdmissionConfig(max_depth=4),
        batching=sv.BatcherConfig(max_batch=4, max_wait_s=1.0))
    lows = [sv.Request(image=_img(fill=i), priority=0) for i in range(4)]
    for r in lows:
        assert sched.submit(r) is None
    hi = sv.Request(image=_img(fill=99), priority=1)
    assert sched.submit(hi) is None       # preempts, not rejected
    preempted = [o for o in sched.outcomes if isinstance(o, sv.Shed)]
    assert len(preempted) == 1 and preempted[0].reason == "preempted"
    sched.drain()
    done = {o.rid: o for o in sched.outcomes}
    assert isinstance(done[hi.rid], sv.Completed)


# ------------------------------------------------- traffic / reports --

def test_make_arrivals_deterministic_and_ordered():
    a = sv.make_arrivals(sv.MIXED_MIX, n=32, seed=5)
    b = sv.make_arrivals(sv.MIXED_MIX, n=32, seed=5)
    assert [t for t, _ in a] == [t for t, _ in b]
    assert [t for t, _ in a] == sorted(t for t, _ in a)
    for (_, ra), (_, rb) in zip(a, b):
        np.testing.assert_array_equal(ra.image, rb.image)
        assert ra.deadline - rb.deadline == 0.0
        assert ra.priority == rb.priority
    sizes = {ra.image.shape[0] for _, ra in a}
    assert sizes <= {32, 64, 128} and 32 in sizes


def test_empty_traffic_report_is_well_formed():
    clk, ex, est = _sim()
    sched = sv.Scheduler(ex, clock=clk, estimator=est)
    rep = sv.run_traffic(sched, [], "empty")
    assert rep.offered == 0
    assert rep.goodput_mpix_per_s == 0.0
    assert rep.reject_rate == rep.shed_rate == 0.0
    assert np.isnan(rep.p50_s) and np.isnan(rep.p99_s)
    rec = rep.record()
    assert rec["p50_ms"] is None and rec["p99_ms"] is None
    assert "offered" in rep.summary()


def test_report_record_shape():
    rep, _, _ = _traffic_cell(100.0, n=40, seed=9)
    rec = rep.record(load_x=0.2, backend="sim")
    assert rec["op"] == "serve_traffic" and rec["mix"] == "cell"
    assert rec["load_x"] == 0.2 and rec["backend"] == "sim"
    assert rec["completed"] == 40 and rec["offered"] == 40
    assert rec["p99_ms"] > 0 and rec["goodput_mpix_per_s"] > 0
    assert rec["reject_rate"] == 0.0 and rec["deadline_miss_rate"] == 0.0


def test_plan_executor_end_to_end():
    """Production wiring: the scheduler drives real compiled plans
    (numpy backend) and the outputs match a direct pipeline call."""
    from repro.imgproc.plan import PIPELINES, compile_pipeline
    from repro.image.pipeline import synthetic_image
    ex = sv.PlanExecutor.compile(("pipe_blur_sharpen_down",),
                                 backend="numpy")
    clk = sv.VirtualClock()
    sched = sv.Scheduler(ex, clock=clk,
                         batching=sv.BatcherConfig(max_batch=2))
    imgs = [synthetic_image(32, seed=40 + i) for i in range(3)]
    reqs = [sv.Request(image=im) for im in imgs]
    for r in reqs:
        assert sched.submit(r) is None
    sched.drain()
    pipe = compile_pipeline(PIPELINES["pipe_blur_sharpen_down"],
                            kind="haloc_axa", backend="numpy")
    golden = np.asarray(pipe(np.stack(imgs)))
    done = {o.rid: o for o in sched.outcomes}
    for i, r in enumerate(reqs):
        assert isinstance(done[r.rid], sv.Completed)
        np.testing.assert_array_equal(done[r.rid].output, golden[i])
    with pytest.raises(KeyError, match="unknown pipeline"):
        ex(np.stack(imgs), "nope")


# -------------------------------------------------------- observability --

def test_serving_metrics_and_spans(fresh_obs, capture):
    with capture() as cap:
        rep, _, _ = _traffic_cell(1200.0, n=120, seed=6, depth=12,
                                  backlog_s=0.010)
    snap = obs.metrics_snapshot(prefix="serve.")
    c = snap["counters"]
    assert c["serve.completed"] == len(rep.completed)
    assert c.get("serve.rejected", 0) == len(rep.rejected)
    assert c.get("serve.shed", 0) == len(rep.shed)
    assert len(rep.rejected) + len(rep.shed) > 0
    assert c["serve.admitted"] == rep.offered - len(rep.rejected)
    assert snap["histograms"]["serve.batch_occupancy"]["count"] > 0
    assert snap["histograms"]["serve.queue_wait_s"]["count"] == \
        len(rep.completed)
    assert snap["gauges"]["serve.queue_depth"]["value"] == 0
    assert all(k.startswith("serve.") for t in ("counters", "gauges",
                                                "histograms")
               for k in snap[t])
    names = {e.name for e in cap.events}
    assert {"serve:submit", "serve:batch", "serve:execute"} <= names


def test_serving_is_zero_cost_when_telemetry_off(capture):
    obs.reset_all()
    assert not obs.enabled()
    # The serving layer's spans and metrics switch with telemetry: a
    # live capture alone records none of them.
    with capture() as cap:
        rep, _, _ = _traffic_cell(100.0, n=30, seed=8)
    assert len(rep.completed) == 30
    snap = obs.metrics_snapshot()
    assert not any(k.startswith("serve.") for k in snap["counters"])
    assert not [e for e in cap.events if e.name.startswith("serve:")]


def test_metrics_snapshot_prefix_filter(fresh_obs):
    obs.counter("serve.x").inc(3)
    obs.counter("stream.y").inc(2)
    obs.gauge("serve.g").set(1)
    full = obs.metrics_snapshot()
    assert "caches" in full and "stream.y" in full["counters"]
    flt = obs.metrics_snapshot(prefix="serve.")
    assert flt["counters"] == {"serve.x": 3}
    assert set(flt["gauges"]) == {"serve.g"}
    assert "caches" not in flt


# -------------------------------------------------- config validation --

def test_config_validation_rejects_bad_knobs():
    with pytest.raises(ValueError, match="max_depth"):
        sv.AdmissionConfig(max_depth=0)
    with pytest.raises(ValueError, match="max_backlog_s"):
        sv.AdmissionConfig(max_backlog_s=0.0)
    with pytest.raises(ValueError, match="max_batch"):
        sv.BatcherConfig(max_batch=0)
    with pytest.raises(ValueError, match="max_wait_s"):
        sv.BatcherConfig(max_wait_s=-1)
    with pytest.raises(ValueError, match="safety"):
        sv.BatcherConfig(safety=0.0)
    with pytest.raises(ValueError, match="max_retries"):
        sv.SchedulerConfig(max_retries=-1)
    with pytest.raises(ValueError, match="backoff_s"):
        sv.SchedulerConfig(backoff_s=-0.1)
    with pytest.raises(ValueError, match="timeout_factor"):
        sv.SchedulerConfig(timeout_factor=0.0)
    with pytest.raises(ValueError, match="failure_threshold"):
        sv.BreakerConfig(failure_threshold=0)
    with pytest.raises(ValueError, match="cooldown_s"):
        sv.BreakerConfig(cooldown_s=-1)
    with pytest.raises(ValueError, match="probe_successes"):
        sv.BreakerConfig(probe_successes=0)
    with pytest.raises(ValueError, match="rate_rps"):
        sv.TrafficMix("bad", rate_rps=0.0)
    with pytest.raises(ValueError, match="sizes"):
        sv.TrafficMix("bad", rate_rps=1.0, sizes=())


def test_straggler_monitor_configs_are_not_shared():
    """Satellite regression: the default StragglerConfig must be
    per-instance — a mutable default evaluated at def time would alias
    every monitor in the process."""
    a = StragglerMonitor()
    b = StragglerMonitor()
    assert a.cfg is not b.cfg
    a.cfg.window = 7
    assert b.cfg.window == 32


@pytest.mark.slow
def test_long_overload_campaign_stays_bounded():
    """10x the quick overload cell, still virtual time: the shedding
    contract must hold over a long campaign, not just the smoke run —
    no unbounded queue, bounded accepted-latency, goodput sustained."""
    base, _, _ = _traffic_cell(100.0, n=800, seed=3)
    over, sched, _ = _traffic_cell(1200.0, n=4000, seed=4,
                                   depth=12, backlog_s=0.010)
    assert over.offered == 4000
    assert len(sched.queue) == 0
    assert len(over.rejected) > 0 and len(over.shed) > 0
    assert over.p99_s <= 3.0 * base.p99_s
    assert over.goodput_mpix_per_s > base.goodput_mpix_per_s
    for o in over.completed:
        assert o.started < o.request.deadline

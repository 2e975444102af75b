"""Deadline-aware request scheduler: the serving front door.

Composition (one request's life):

    submit ── admission control ──► bounded queue (priority, per-bucket)
                    │ Rejected (backpressure, typed)
                    ▼
             deadline-aware batcher ──► shed expired / doomed / preempted
                    │ Batch (same pipeline × shape, oldest first)
                    ▼
             circuit breaker gate ──► open: hold + degrade Pareto rung
                    │ allowed (closed, or the half-open probe)
                    ▼
             execute (timeout verdict via StragglerMonitor.late,
                      bounded exponential-backoff retries,
                      poisoned-request isolation on exhaustion)
                    ▼
             Completed / Failed outcomes

The scheduler is single-threaded and clock-driven: ``submit`` admits,
``pump`` forms and runs every batch that is due at the current clock
instant, ``drain`` finishes everything still queued.  All waiting flows
through the injected :class:`~repro.serving.clock.Clock`, so the whole
machine — backoff, breaker cooldowns, deadline expiry — runs
deterministically on a :class:`~repro.serving.clock.VirtualClock` in
tests and on wall time in production.

Invariants the tests pin down:

- queue depth and estimated backlog latency are bounded (admission);
- a request whose deadline has expired is NEVER dispatched — it is
  shed before every attempt, including retries;
- lateness/timeout verdicts route through the repo-wide
  :meth:`repro.runtime.straggler.StragglerMonitor.late`;
- a poisoned request takes down only itself: after batch-level retries
  exhaust, the batch is split and survivors complete individually.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Sequence

import numpy as np

from repro.obs import metrics as _metrics
from repro.obs import trace as _obs
from repro.serving.batcher import Batch, Batcher, BatcherConfig
from repro.serving.breaker import CircuitBreaker
from repro.serving.clock import Clock, WallClock
from repro.serving.estimator import CostEstimator
from repro.serving.queue import AdmissionConfig, AdmissionQueue
from repro.serving.request import (Completed, Failed, Outcome, Rejected,
                                   Request, Shed)


@dataclasses.dataclass(frozen=True)
class SchedulerConfig:
    """Execution-hardening knobs.

    Attributes:
      max_retries: batch re-dispatches after a raising attempt (the
        whole batch retries with exponential backoff; exhaustion
        triggers poisoned-request isolation).
      backoff_s: base backoff — attempt ``k`` sleeps
        ``backoff_s * 2**k`` before re-dispatching.
      timeout_factor: a batch's timeout is its estimated service time
        times this factor; the verdict is
        ``StragglerMonitor.late(service, deadline=timeout)``.
    """

    max_retries: int = 1
    backoff_s: float = 0.005
    timeout_factor: float = 4.0

    def __post_init__(self):
        if self.max_retries < 0:
            raise ValueError(
                f"max_retries must be >= 0; got {self.max_retries}")
        if self.backoff_s < 0:
            raise ValueError(
                f"backoff_s must be >= 0; got {self.backoff_s}")
        if self.timeout_factor <= 0:
            raise ValueError(
                f"timeout_factor must be > 0; got {self.timeout_factor}")


class Scheduler:
    """Deadline-aware dynamic-batching scheduler over an executor.

    Args:
      executor: ``(images, pipeline) -> outputs`` — a
        :class:`~repro.serving.executor.PlanExecutor` in production, a
        :class:`~repro.serving.executor.SimExecutor` in tests.
      clock: time source (default wall clock).
      estimator: service-time model shared by admission, batching and
        timeouts (default: a fresh EWMA estimator).
      admission / batching / config: knob dataclasses.
      breaker: optional :class:`~repro.serving.breaker.CircuitBreaker`
        (attach a ``DegradePolicy`` to it for Pareto-rung fallback).
      straggler: optional :class:`~repro.runtime.straggler
        .StragglerMonitor`; defaults to a deadline-only monitor, so
        the one ``late`` definition judges serving timeouts too.
      integrity: optional integrity watchdog(s) — anything with the
        ``maybe_run(now)`` cadence protocol
        (:class:`~repro.integrity.scrub.LutScrubber`,
        :class:`~repro.integrity.canary.CanarySuite`); ticked at the
        top of every :meth:`pump` on the scheduler's clock, so scrub
        and canary cadences ride the serving loop with no extra thread.
    """

    def __init__(self, executor, *, clock: Optional[Clock] = None,
                 estimator: Optional[CostEstimator] = None,
                 admission: Optional[AdmissionConfig] = None,
                 batching: Optional[BatcherConfig] = None,
                 config: Optional[SchedulerConfig] = None,
                 breaker: Optional[CircuitBreaker] = None,
                 straggler=None, integrity=None):
        from repro.runtime.straggler import (StragglerConfig,
                                             StragglerMonitor)
        self.executor = executor
        self.clock = clock if clock is not None else WallClock()
        self.estimator = estimator if estimator is not None \
            else CostEstimator()
        self.queue = AdmissionQueue(admission, self.estimator)
        self.batcher = Batcher(batching, self.estimator)
        self.config = config if config is not None else SchedulerConfig()
        self.breaker = breaker
        self.straggler = straggler if straggler is not None else \
            StragglerMonitor(StragglerConfig(min_samples=1 << 30))
        if integrity is None:
            self.integrity = ()
        elif hasattr(integrity, "maybe_run"):
            self.integrity = (integrity,)
        else:
            self.integrity = tuple(integrity)
        self.outcomes: List[Outcome] = []
        self._batch_seq = 0

    # ---------------------------------------------------------- submit --

    def submit(self, request: Request) -> Optional[Rejected]:
        """Admit ``request`` (stamping its arrival) or refuse it.

        Returns the typed :class:`Rejected` on refusal, ``None`` on
        admission.  Either way the verdict also lands in
        :attr:`outcomes` (as does a ``Shed`` for any lower-priority
        request the admission preempted)."""
        now = self.clock.now()
        req = dataclasses.replace(request, arrival=now)
        instrumented = _obs._ENABLED
        if instrumented:
            with _obs.span("serve:submit", rid=req.rid,
                           pipeline=req.pipeline):
                rejected, evicted = self.queue.offer(req)
        else:
            rejected, evicted = self.queue.offer(req)
        if evicted is not None:
            self._emit(Shed(evicted, reason="preempted", at=now),
                       instrumented)
        if rejected is not None:
            self._emit(rejected, instrumented)
            return rejected
        if instrumented:
            _metrics.counter("serve.admitted").inc()
            _metrics.gauge("serve.queue_depth").set(self.queue.depth)
        return None

    # ------------------------------------------------------------ pump --

    def pump(self, *, force: bool = False) -> List[Outcome]:
        """Shed stale work, then form and execute every batch due at
        the current clock instant.  ``force`` dispatches partial
        batches immediately (the drain path).  Returns the outcomes
        produced by THIS call (also appended to :attr:`outcomes`)."""
        instrumented = _obs._ENABLED
        produced: List[Outcome] = []
        now = self.clock.now()
        # Integrity watchdogs tick before any dispatch: a scrub/canary
        # detection this instant can trip the breaker and block the
        # batches below from running on a corrupted datapath.
        for watchdog in self.integrity:
            watchdog.maybe_run(now)
        for shed in self.batcher.shed(self.queue, now):
            self._emit(shed, instrumented)
            produced.append(shed)
        if self.breaker is not None and not self.breaker.allow(now):
            if instrumented:
                _metrics.gauge("serve.queue_depth").set(self.queue.depth)
            return produced
        limit = 1 if (self.breaker is not None
                      and self.breaker.probing) else None
        batches = self.batcher.collect(self.queue, now, force=force,
                                       limit=limit)
        for batch in batches:
            if instrumented:
                _metrics.histogram("serve.batch_occupancy").record(
                    len(batch))
                with _obs.span("serve:batch", pipeline=batch.pipeline,
                               size=len(batch)):
                    out = self._run_batch(batch)
            else:
                out = self._run_batch(batch)
            for o in out:
                self._emit(o, instrumented)
            produced.extend(out)
        if instrumented:
            _metrics.gauge("serve.queue_depth").set(self.queue.depth)
        return produced

    def drain(self) -> List[Outcome]:
        """Run until the queue is empty (partial batches dispatch
        immediately; an open breaker waits out its cooldown on the
        scheduler clock so the half-open probe can run)."""
        produced: List[Outcome] = []
        while len(self.queue):
            produced.extend(self.pump(force=True))
            if len(self.queue) and self.breaker is not None:
                wait = self.breaker.retry_after(self.clock.now())
                if wait > 0:
                    self.clock.sleep(wait)
        return produced

    # ------------------------------------------------------- internals --

    def _emit(self, outcome: Outcome, instrumented: bool) -> None:
        self.outcomes.append(outcome)
        if not instrumented:
            return
        if isinstance(outcome, Rejected):
            _metrics.counter("serve.rejected").inc()
        elif isinstance(outcome, Shed):
            _metrics.counter("serve.shed").inc()
            _metrics.counter(f"serve.shed.{outcome.reason}").inc()
        elif isinstance(outcome, Failed):
            _metrics.counter("serve.failed").inc()
        elif isinstance(outcome, Completed):
            _metrics.counter("serve.completed").inc()
            _metrics.histogram("serve.queue_wait_s").record(
                outcome.queue_wait)
            _metrics.histogram("serve.latency_s").record(outcome.latency)
            if outcome.missed_deadline:
                _metrics.counter("serve.deadline_misses").inc()

    def _shed_expired(self, requests: Sequence[Request], now: float
                      ) -> List[Outcome]:
        """The no-doomed-work guarantee, applied immediately before an
        attempt: an expired request is shed, never executed."""
        return [Shed(r, reason="expired", at=now)
                for r in requests if now >= r.deadline]

    def _run_batch(self, batch: Batch) -> List[Outcome]:
        cfg = self.config
        instrumented = _obs._ENABLED
        outcomes: List[Outcome] = []
        requests = list(batch.requests)
        timeout = self.estimator.estimate(batch.pixels) \
            * cfg.timeout_factor
        self._batch_seq += 1
        seq = self._batch_seq
        attempt = 0
        last_error = ""
        while True:
            now = self.clock.now()
            expired = self._shed_expired(requests, now)
            if expired:
                outcomes.extend(expired)
                gone = {o.rid for o in expired}
                requests = [r for r in requests if r.rid not in gone]
            if not requests:
                return outcomes
            if self.breaker is not None and not self.breaker.allow(now):
                # The breaker opened mid-batch (this batch's own
                # failures tripped it): survivors are victims of a sick
                # backend, not poison — back to the queue to await the
                # half-open probe.  Re-entry skips admission: they were
                # already admitted once.
                for r in requests:
                    self.queue.requeue(r)
                return outcomes
            images = np.stack([r.image for r in requests])
            t0 = self.clock.now()
            try:
                if instrumented:
                    with _obs.span("serve:execute",
                                   pipeline=batch.pipeline,
                                   size=len(requests), attempt=attempt):
                        out = self.executor(images, batch.pipeline)
                else:
                    out = self.executor(images, batch.pipeline)
            except Exception as exc:
                last_error = str(exc)
                if self.breaker is not None:
                    self.breaker.record_failure(self.clock.now())
                if attempt < cfg.max_retries:
                    if instrumented:
                        _metrics.counter("serve.retries").inc()
                    self.clock.sleep(cfg.backoff_s * (2 ** attempt))
                    attempt += 1
                    continue
                if len(requests) > 1:
                    outcomes.extend(self._isolate(requests,
                                                  batch.pipeline,
                                                  attempt + 1))
                else:
                    outcomes.append(Failed(requests[0], error=last_error,
                                           attempts=attempt + 1))
                return outcomes
            finished = self.clock.now()
            service = finished - t0
            self.estimator.observe(int(images.size), service)
            late = self.straggler.late(seq, service, deadline=timeout)
            if late and instrumented:
                _metrics.counter("serve.stragglers").inc()
            if self.breaker is not None:
                self.breaker.record_success(finished)
            out = np.asarray(out)
            for i, r in enumerate(requests):
                outcomes.append(Completed(
                    r, output=out[i], started=t0, finished=finished,
                    queue_wait=t0 - r.arrival, service_s=service,
                    attempts=attempt + 1, late=late))
            return outcomes

    def _isolate(self, requests: Sequence[Request], pipeline: str,
                 attempts: int) -> List[Outcome]:
        """Batch-level retries exhausted: split the batch and run each
        request alone ONCE, so one poisoned input fails alone and its
        neighbors still complete (PR 8's ``isolate`` semantics at the
        request granularity)."""
        instrumented = _obs._ENABLED
        if instrumented:
            _metrics.counter("serve.isolations").inc()
        outcomes: List[Outcome] = []
        for r in requests:
            now = self.clock.now()
            if now >= r.deadline:
                outcomes.append(Shed(r, reason="expired", at=now))
                continue
            t0 = now
            try:
                if instrumented:
                    with _obs.span("serve:isolate", rid=r.rid,
                                   pipeline=pipeline):
                        out = self.executor(r.image[None], pipeline)
                else:
                    out = self.executor(r.image[None], pipeline)
            except Exception as exc:
                if self.breaker is not None:
                    self.breaker.record_failure(self.clock.now())
                outcomes.append(Failed(r, error=str(exc),
                                       attempts=attempts + 1))
                continue
            finished = self.clock.now()
            if self.breaker is not None:
                self.breaker.record_success(finished)
            self.estimator.observe(r.pixels, finished - t0)
            outcomes.append(Completed(
                r, output=np.asarray(out)[0], started=t0,
                finished=finished, queue_wait=t0 - r.arrival,
                service_s=finished - t0, attempts=attempts + 1))
        return outcomes

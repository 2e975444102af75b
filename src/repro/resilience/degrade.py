"""Self-healing degradation: drift-triggered fallback down the Pareto
ladder.

The serving story this closes: a pipeline is deployed on an approximate
adder config chosen from the PR-5 exact energy/accuracy frontier.  A
hardware defect (or a mis-budgeted config) pushes its measured per-add
error outside the config's exact band — the installed
:class:`~repro.obs.drift.DriftMonitor` trips.  Instead of serving
garbage, :class:`DegradePolicy` swaps the compiled plan for the
NEXT-CHEAPEST config on the exact Pareto frontier that is strictly more
accurate than the current one — ultimately the exact adder — re-budgets
the monitor to the new config, and serves the batch that tripped on
the new plan.  Energy degrades one frontier rung at a time; quality
recovers immediately.

Fallback plans compile WITHOUT the fault: degrading models swapping the
defective approximate block for a different (healthy) operating point,
which is exactly why the recovery is measurable.  If the replacement
config itself drifts out of ITS band (pathological inputs, another
defect), the policy escalates to the next rung, so the ladder ends at
the exact adder where the error budget is zero and the monitor can
never trip again.

Everything here is deterministic: the ladder is a pure function of the
spec (closed-form analytics, no sampling) and the monitor's verdict is
a pure function of the observations, so a seeded campaign replays
bit-identically.
"""

from __future__ import annotations

import functools
from typing import List, Optional, Tuple

import numpy as np

from repro.core.hwcost import switching_energy_fj
from repro.core.specs import AdderSpec
from repro.obs import drift as _drift
from repro.obs import metrics as _metrics
from repro.obs import trace as _obs

__all__ = ["DegradePolicy", "pareto_ladder"]


@functools.lru_cache(maxsize=None)
def pareto_ladder(spec: AdderSpec) -> Tuple[AdderSpec, ...]:
    """Fallback sequence for ``spec``: the exact energy/NMED Pareto
    frontier at ``spec.n_bits``, restricted to configs strictly more
    accurate than ``spec``, cheapest first, ending at the exact adder.

    Built entirely from the PR-5 closed-form analytics
    (:func:`~repro.ax.analytics.exact_error_metrics_sweep`) — no
    sampling, so the ladder is deterministic and cacheable.  The
    frontier rule matches ``benchmarks/fig6_tradeoff.pareto``: sort by
    switching energy ascending, keep points whose NMED strictly
    improves.

    Candidates are capped at ``m <= spec.lsm_bits``: a fallback must be
    strictly MORE accurate than ``spec``, and widening the approximate
    section only moves the other way — so the cap discards nothing a
    ladder could use while keeping the exact sweep to the cheap corner
    of the design space."""
    from repro.ax.analytics import design_space, exact_error_metrics, \
        exact_error_metrics_sweep
    from repro.ax.registry import get_adder

    max_lsm = None if get_adder(spec.kind).is_exact else spec.lsm_bits
    candidates = design_space(n_bits=(spec.n_bits,), max_lsm=max_lsm)
    reports = exact_error_metrics_sweep(candidates, cache_tables=False)
    rows = sorted(((switching_energy_fj(r.spec), r.nmed, r.spec)
                   for r in reports), key=lambda t: (t[0], t[1]))
    frontier: List[Tuple[float, float, AdderSpec]] = []
    best = float("inf")
    for energy, nmed, s in rows:
        if nmed < best:
            frontier.append((energy, nmed, s))
            best = nmed
    own = exact_error_metrics(spec, cache_tables=False).nmed
    ladder = tuple(s for _, nmed, s in frontier
                   if nmed < own and s != spec)
    if not ladder:
        raise ValueError(
            f"no fallback exists for {spec.short_name}: nothing on the "
            f"N={spec.n_bits} Pareto frontier beats its NMED ({own:.3e})"
            " — it is already exact (or exact-equivalent)")
    return ladder


class DegradePolicy:
    """Drift-triggered plan degradation for a compiled pipeline.

    Args:
      pipe: the deployed :class:`~repro.imgproc.plan.CompiledPipeline`
        (possibly fault-injected — that is the scenario this exists
        for).
      band / z / min_samples: forwarded to the
        :class:`~repro.obs.drift.DriftMonitor` budgeted against the
        CURRENT plan's spec (re-budgeted on every fallback).
      observe_crop: side of the square corner crop shadow-run per
        observation — keeps the numpy twin cheap while feeding the
        monitor thousands of per-add error samples per batch.
      ladder: override the fallback sequence (default
        :func:`pareto_ladder` of the pipe's spec).

    Usage: serve a stream through :meth:`stream`, or drive it manually
    — ``observe(batch)`` returns True the moment a fallback swap
    happened, and :meth:`run` then serves the batch on the recovered
    plan.  Requires
    live telemetry (:func:`repro.obs.trace.enable`): drift capture is
    compiled out otherwise, and silently observing nothing would defeat
    the whole point, so :meth:`observe` refuses to run blind.
    """

    def __init__(self, pipe, *, band: float = 1.25, z: float = 4.0,
                 min_samples: int = 1024, observe_crop: int = 32,
                 ladder: Optional[Tuple[AdderSpec, ...]] = None):
        self.base = pipe
        self.pipe = pipe
        self.band = float(band)
        self.z = float(z)
        self.min_samples = int(min_samples)
        self.observe_crop = int(observe_crop)
        if self.observe_crop < 4:
            raise ValueError(
                f"observe_crop must be >= 4 pixels; got {observe_crop}")
        self.ladder = tuple(ladder) if ladder is not None \
            else pareto_ladder(pipe.engine.spec)
        self.level = 0
        self.trips = 0
        self.monitor = self._budget(pipe.engine.spec)
        self._shadow = self._numpy_twin(pipe, keep_fault=True)

    # ------------------------------------------------------- internals --

    def _budget(self, spec: AdderSpec) -> _drift.DriftMonitor:
        return _drift.DriftMonitor(spec, band=self.band, z=self.z,
                                   min_samples=self.min_samples)

    def _numpy_twin(self, pipe, keep_fault: bool):
        """The numpy-backend shadow of ``pipe`` — same stages, requant,
        spec and (optionally) fault, but with concrete host arrays so
        the drift capture hooks see real values, not jit tracers."""
        from repro.imgproc.plan import compile_pipeline
        stages = [(name, dict(kw)) for name, kw in pipe.stages]
        return compile_pipeline(
            stages, kind=pipe.engine.spec, backend="numpy",
            strategy=pipe.engine.strategy, requant=pipe.requant,
            fault=pipe.engine.fault if keep_fault else None)

    def _fallback(self) -> None:
        """Swap to the next ladder rung: recompile the plan at the new
        spec WITHOUT the fault, re-budget the monitor, re-shadow."""
        from repro.imgproc.plan import compile_pipeline
        spec = self.ladder[self.level]
        self.level += 1
        stages = [(name, dict(kw)) for name, kw in self.base.stages]
        self.pipe = compile_pipeline(
            stages, kind=spec, backend=self.base.engine.backend.name,
            strategy=self.base.engine.strategy, requant=self.base.requant,
            fault=None)
        self.monitor = self._budget(spec)
        self._shadow = self._numpy_twin(self.pipe, keep_fault=False)

    def _trip(self) -> None:
        """One recorded trip + fallback swap + metrics (shared by the
        drift path and the externally-commanded path)."""
        self.trips += 1
        _metrics.counter("degrade.trips").inc()
        self._fallback()
        _metrics.counter("degrade.fallbacks").inc()
        _metrics.gauge("degrade.level").set(self.level)

    # ------------------------------------------------------------- API --

    @property
    def exhausted(self) -> bool:
        """No rungs left — the policy is already at its most accurate
        (normally exact) config."""
        return self.level >= len(self.ladder)

    def force_fallback(self) -> bool:
        """Externally-commanded degradation: step one rung down the
        ladder WITHOUT a drift observation — the serving circuit
        breaker's trip action (consecutive executor failures are
        evidence of a sick operating point even when no drift sample
        exists).  Returns False (and does nothing) when the ladder is
        exhausted.  Unlike :meth:`observe`, needs no live telemetry:
        there is no shadow capture involved."""
        if self.exhausted:
            return False
        self._trip()
        return True

    def on_integrity_alarm(self, report=None) -> bool:
        """An integrity detection (LUT scrub hit, failed canary, ABFT
        flag) from :mod:`repro.integrity`: observed corruption in the
        datapath is at least as damning as measured drift, so step one
        rung down the ladder.  ``report`` (a Scrub/Canary report) is
        accepted for the alarm-feed signature and recorded in metrics
        only.  Needs no live telemetry, like :meth:`force_fallback`."""
        if _obs._ENABLED:
            _metrics.counter("degrade.integrity_alarms").inc()
        return self.force_fallback()

    def observe(self, batch) -> bool:
        """Feed one batch's evidence to the drift monitor; returns True
        when this observation TRIPPED it and a fallback swap just
        happened (the caller should re-run the batch through
        :meth:`run`).

        Evidence comes from shadow-running a corner crop of the batch
        on the numpy twin of the CURRENT plan (fault included) with the
        monitor installed — the capture hooks then compare every
        approximate add against its exact integer twin."""
        if not _obs._ENABLED:
            raise RuntimeError(
                "DegradePolicy.observe needs live telemetry — call "
                "repro.obs.trace.enable() (drift capture is compiled "
                "out when tracing is off, so observing would be blind)")
        c = self.observe_crop
        crop = np.asarray(batch)[..., :c, :c]
        with _drift.installed(self.monitor):
            self._shadow(crop)
        if self.monitor.ok() or self.exhausted:
            return False
        self._trip()
        _metrics.counter("degrade.retries").inc()
        return True

    def run(self, batch):
        """Execute the CURRENT plan (base, or fallback after a trip)."""
        return self.pipe(batch)

    def stream(self, batches) -> Tuple[List[np.ndarray], Tuple[int, ...]]:
        """Serve ``batches`` in order: observe each, then run it on the
        current plan, so the batch that trips is served by the
        recovered plan, as is every later one.  Returns the host
        outputs and the indices served while degraded."""
        outs: List[np.ndarray] = []
        degraded: List[int] = []
        for i, batch in enumerate(batches):
            self.observe(batch)
            outs.append(np.asarray(self.run(batch)))
            if self.level:
                degraded.append(i)
        return outs, tuple(degraded)

    def __repr__(self) -> str:
        cur = self.pipe.engine.spec.short_name
        return (f"DegradePolicy(level={self.level}/{len(self.ladder)}, "
                f"current={cur}, trips={self.trips})")

"""``repro.obs`` — structured telemetry for the execution stack.

Three pillars, instrumented through engine → plan → tiles → streaming:

1. **Tracing** (:mod:`repro.obs.trace`): spans (``obs.span("plan:call")``)
   are ``jax.profiler`` annotations, so they land in the profiler's
   trace beside the device's operations, on its clock, and record
   whenever a capture is live.  Inside jitted code the compiled plan
   labels each stage's operations with ``jax.named_scope("stage:<name>")``
   instead, which the device trace carries per operation.  Capture with
   ``jax.profiler.trace(dir)`` and open the result in Perfetto or
   TensorBoard.
2. **Metrics** (:mod:`repro.obs.metrics`): named counters / gauges /
   histograms (pixels processed, batches in flight, per-batch latency
   percentiles) plus a named cache-stats facade
   (:mod:`repro.obs.caches`) over every ``lru_cache`` site — engine
   handles, LUT tables, compiled plans, tiled executors.
3. **Quality drift** (:mod:`repro.obs.drift`): an online per-stage
   mean-error monitor against the PR-5 exact MED/NMED budgets of the
   active ``(kind, m, k)`` config — the runtime counterpart of
   ``fused_psnr_gate``.

Metrics and drift capture switch with one module-level flag
(:func:`enable` / :func:`disable`, or ``REPRO_OBS=1`` in the
environment); off, they are shared no-ops.  A span costs one flag test
and the profiler's "is a capture live" test when neither is on.  The
off overhead on the megapixel streaming benchmark is measured and
bounded by ``benchmarks/bench_imgproc.py`` (telemetry cell) and
``benchmarks/check_overhead.py``.

    import jax
    from repro import obs

    obs.enable()                          # metrics and drift capture
    with jax.profiler.trace("prof"):      # spans and device operations
        ...run pipelines / streams...
    obs.write_metrics("metrics.json")
    print(obs.format_cache_stats())
"""

from __future__ import annotations

import os

from repro.obs.caches import (  # noqa: F401
    cache_names,
    cache_stats,
    format_cache_stats,
    get_cached,
    register_lru,
)
from repro.obs.drift import (  # noqa: F401
    DriftMonitor,
    DriftStatus,
    active_monitor,
    install,
    installed,
    uninstall,
)
from repro.obs.metrics import (  # noqa: F401
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    counter,
    gauge,
    histogram,
    metrics_snapshot,
    quantile,
    registry,
    reset_metrics,
    write_metrics,
)
from repro.obs.trace import (  # noqa: F401
    current_span,
    current_stack,
    disable,
    enable,
    enabled,
    live,
    span,
)


class _TelemetryScope:
    """``with obs.telemetry(): ...`` — enable, then restore on exit."""

    def __init__(self, on: bool):
        self._on = on

    def __enter__(self):
        self._was = enabled()
        enable() if self._on else disable()
        return self

    def __exit__(self, *exc):
        enable() if self._was else disable()
        return False


def telemetry(on: bool = True) -> _TelemetryScope:
    """Scoped enable/disable (restores the previous flag state)."""
    return _TelemetryScope(on)


def reset_all() -> None:
    """Clear recorded metrics (spans live in the profiler's capture;
    cache stats are live views and are not resettable from here)."""
    reset_metrics()


__all__ = [
    "Counter", "DriftMonitor", "DriftStatus", "Gauge", "Histogram",
    "MetricsRegistry", "active_monitor", "cache_names", "cache_stats",
    "counter", "current_span", "current_stack", "disable", "enable",
    "enabled", "format_cache_stats", "gauge", "get_cached", "histogram",
    "install", "installed", "live", "metrics_snapshot", "quantile",
    "register_lru", "registry", "reset_all", "reset_metrics", "span",
    "telemetry", "uninstall", "write_metrics",
]

if os.environ.get("REPRO_OBS", "") not in ("", "0"):
    enable()

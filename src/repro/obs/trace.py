"""Spans on the profiler's clock.

The tracing pillar of :mod:`repro.obs`.  ``obs.span(name, **args)`` is a
``jax.profiler.TraceAnnotation``: while a profiler capture is live
(``jax.profiler.trace(dir)``, ``jax.profiler.start_trace``) it records
one host event, with ``args`` as the event's stats, into the same trace
as the device's operations and on the same clock.  Open the capture in
Perfetto or TensorBoard's profile plugin.

A span records whenever a capture is live, whether or not telemetry is
on.  With no capture and telemetry off, :func:`span` returns one shared
no-op after a flag test and the profiler's own "is a capture live" test
(a fraction of a microsecond), so spans can live on hot call paths.
Telemetry (:func:`enable`, ``REPRO_OBS=1``) switches the counters,
gauges, histograms and drift capture; a live span also pushes its name
on a context-var stack, which gives drift capture its stage label
(:func:`current_stack`).

Inside ``jax.jit`` a span would fire once, at trace time, and label no
device operation.  Traced code labels its operations with
``jax.named_scope`` instead (the compiled plan names each stage
``stage:<name>``): every HLO operation, fusions included, carries the
scope in its metadata, and the device trace shows it.

    import jax
    from repro import obs

    with jax.profiler.trace("/tmp/prof"):
        with obs.span("stream:run", batches=8):
            ...
"""

from __future__ import annotations

import contextvars
from typing import Optional, Tuple

from jax.profiler import TraceAnnotation

#: THE module-level telemetry flag (metrics, drift capture, and the
#: spans of the call sites that test it).  Flip via
#: :func:`enable`/:func:`disable`.
_ENABLED = False

#: Whether a profiler capture is live (the profiler's own flag test).
_capturing = TraceAnnotation.is_enabled


def enabled() -> bool:
    """Whether telemetry (metrics, drift capture) is on."""
    return _ENABLED


def enable() -> None:
    """Turn telemetry on (metrics record, drift capture runs)."""
    global _ENABLED
    _ENABLED = True


def disable() -> None:
    """Turn telemetry off; recorded metrics are kept until reset."""
    global _ENABLED
    _ENABLED = False


def live() -> bool:
    """Whether a span records: telemetry is on or a capture is live."""
    return _ENABLED or _capturing()


#: Per-context stack of open span names (nesting + stage attribution
#: for the drift monitor's engine capture).
_STACK: contextvars.ContextVar[Tuple[str, ...]] = \
    contextvars.ContextVar("repro_obs_span_stack", default=())


def current_stack() -> Tuple[str, ...]:
    """Names of the open spans in this context, outermost first."""
    return _STACK.get()


def current_span() -> Optional[str]:
    """The innermost open span name, or ``None``."""
    stack = _STACK.get()
    return stack[-1] if stack else None


class _NoopSpan:
    """The off fast path: a shared, state-free context manager."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False

    def set(self, **kw):  # attribute updates are dropped
        pass


_NOOP = _NoopSpan()


class _Span:
    __slots__ = ("name", "_args", "_annotation", "_tok")

    def __init__(self, name: str, args):
        self.name = name
        self._args = args

    def __enter__(self):
        self._tok = _STACK.set(_STACK.get() + (self.name,))
        self._annotation = TraceAnnotation(self.name, **self._args)
        self._annotation.__enter__()
        return self

    def set(self, **kw):
        """Attach extra stats to the span's event before it closes."""
        self._annotation.set_metadata(**kw)

    def __exit__(self, *exc):
        self._annotation.__exit__(*exc)
        _STACK.reset(self._tok)
        return False


def span(name: str, **args):
    """A profiler-annotation span; the shared no-op unless telemetry is
    on or a capture is live.  ``args`` become the event's stats (values
    that are not numbers or strings are recorded as strings).  Spans
    nest per thread and per context."""
    if not (_ENABLED or _capturing()):
        return _NOOP
    return _Span(name, args)

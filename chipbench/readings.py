"""What a traced run hands the per-layer metric readers, and the
arithmetic they share.

Each reader (``metrics/<metric>.py``) has one function, ``read(r)``,
that takes a :class:`Reading` and returns the metric's value, or
``None`` where the run holds nothing to read (the metric is then left
out of the result line).  A share of a roofline or a peak is never
returned as 0 for want of data.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Dict, List, Optional

from chipbench import peaks, trace as trace_lib
from chipbench.cells import load_module

_WORK_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                         "work")


@dataclasses.dataclass
class Reading:
    trace: trace_lib.Trace
    device: str                   # the trace plane of the chip used
    window: trace_lib.Interval    # the measured window, trace clock (ns)
    device_kind: str
    calls: List[tuple]            # logical shape of each call the window made
    config: dict                  # the cell's configuration
    counters: Dict[str, float]

    @property
    def ops(self):
        return self.trace.devices[self.device]


def idle_share(r: Reading) -> Optional[float]:
    """Percent of the window in which no operation ran on the device."""
    lo, hi = r.window
    if hi <= lo:
        return None
    return 100.0 * (1.0 - trace_lib.busy_ns(r.ops, r.window) / (hi - lo))


def work_of(kernel: str):
    return load_module(os.path.join(_WORK_DIR, kernel + ".py"),
                       f"chipbench_work_{kernel}").work


def _least_seconds(r: Reading, work: str, peak: str) -> float:
    """The least time the chip needs for the logical work
    (``work/<work>.py``) of every call of the window, at ``peak``."""
    count = work_of(work)
    return sum(peaks.bound_seconds(*count(shape, r.config), r.device_kind,
                                   peak)[0] for shape in r.calls)


def roofline(r: Reading, kernel: str) -> Optional[float]:
    """Percent of its roofline that ``kernel`` reached: the least time
    the chip needs for the kernel's logical work in every call of the
    window (``work/<kernel>.py``), over the summed device time of the
    kernel's operations inside the window.  An operation that the
    trace's clocks put across an edge of the window counts its part
    inside: the first call's kernel can appear to start some tens of
    microseconds before the window's span that dispatched it.  ``None``
    where the trace holds none of them or the work is nil, or off the
    chips of the peak table (the run itself refuses an unknown chip)."""
    if r.device_kind not in peaks.PEAKS:
        return None
    bound = _least_seconds(r, kernel, "int8_ops")
    lo, hi = r.window
    seconds = sum(max(0, min(o.end, hi) - max(o.start, lo)) for o in r.ops
                  if o.kernel == kernel) / 1e9
    if bound <= 0 or seconds <= 0:
        return None
    return 100.0 * bound / seconds


def step_share(r: Reading, work: str, peak: str) -> Optional[float]:
    """Percent of the window that the chip needs at least for the logical
    work of every call of the window (``work/<work>.py``): operations at
    the peak rate ``peak`` of ``peaks.PEAKS``, bytes at HBM bandwidth.
    ``None`` where the window made no call, or off the chips of the peak
    table."""
    lo, hi = r.window
    if not r.calls or hi <= lo or r.device_kind not in peaks.PEAKS:
        return None
    return 100.0 * _least_seconds(r, work, peak) / ((hi - lo) / 1e9)


def glue_share(r: Reading) -> Optional[float]:
    """Percent of the device's busy time spent in operations that are
    not Pallas kernels (the XLA work around the kernels)."""
    lo, hi = r.window
    inside = [o for o in r.ops if lo <= o.start < hi]
    total = sum(o.end - o.start for o in inside)
    if total <= 0:
        return None
    glue = sum(o.end - o.start for o in inside if not o.kernel)
    return 100.0 * glue / total

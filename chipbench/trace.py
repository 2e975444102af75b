"""The profiler trace of a run, and its reduction to per-layer numbers.

A traced run records the device's operations and the benchmark's own
host spans (``jax.profiler.TraceAnnotation`` around each call into a
layer) in one trace.  :func:`load` reads the ``.xplane.pb`` that JAX's
profiler writes into a :class:`Trace`: per-device operation intervals
and the host spans, on the profiler's common clock, in nanoseconds.
Everything after that is plain arithmetic on intervals, tested on a
small recorded trace (``tests/chipbench``).

* busy time: the union of the device's operation intervals inside the
  window (an operation that straddles an edge counts its inside part);
* kernel time: the summed durations of the operations that carry a
  kernel's stable name (the Pallas kernel's ``name``);
* idle gaps: the window minus the busy union, each gap charged to the
  innermost host span that covers its midpoint (``host:none`` if none).
"""

from __future__ import annotations

import dataclasses
import glob
import heapq
import json
import os
import re
from typing import Dict, List, Optional, Tuple

#: Host spans written by the benchmark carry this prefix.
SPAN_PREFIX = "cb."

#: The span around the measured window.
WINDOW_SPAN = "cb.window"

#: Trace lines that hold a device's operations (one line per device).
_OPS_LINE = "XLA Ops"

Interval = Tuple[int, int]


@dataclasses.dataclass
class Op:
    name: str          # the operation's name in the trace
    kernel: str        # the kernel or HLO category it belongs to
    start: int
    end: int


@dataclasses.dataclass
class Trace:
    """Device operations (per device) and host spans, in ns."""

    devices: Dict[str, List[Op]]
    spans: List[Tuple[str, int, int]]

    def window(self) -> Optional[Interval]:
        """The measured window: the outermost ``cb.window`` span."""
        ws = [(s, e) for n, s, e in self.spans if n == WINDOW_SPAN]
        if not ws:
            return None
        return min(s for s, _ in ws), max(e for _, e in ws)

    def to_json(self) -> dict:
        return {"devices": {d: [[o.name, o.kernel, o.start, o.end]
                                for o in ops]
                            for d, ops in self.devices.items()},
                "spans": [list(s) for s in self.spans]}

    @classmethod
    def from_json(cls, data: dict) -> "Trace":
        return cls(devices={d: [Op(*o) for o in ops]
                            for d, ops in data["devices"].items()},
                   spans=[tuple(s) for s in data["spans"]])


def _stat(event, key: str) -> Optional[str]:
    for k, v in event.stats:
        if k == key:
            return str(v)
    return None


#: Kernels whose work the benchmark counts (``work/<kernel>.py``).
_KNOWN = frozenset(os.path.splitext(n)[0] for n in os.listdir(
    os.path.join(os.path.dirname(os.path.abspath(__file__)), "work")))


def op_name(name: str) -> str:
    """The HLO instruction's own name.  A TPU trace may give an
    operation the whole instruction as its name (``%conv_chain.3 =
    s32[4,1,1024,1024]{...} custom-call(...), custom_call_target=
    "tpu_custom_call", ...``); the name is the part before ``=``."""
    m = re.match(r"%?([^\s=]+)", name)
    return m.group(1) if m else name


def kernel_of(name: str, texts: str = "") -> str:
    """The Pallas kernel an operation runs, ``""`` for an XLA operation.

    XLA names a Mosaic custom call after the ``pallas_call``'s ``name``
    (``conv_chain.2``; an unnamed one after its jitted caller, such as
    ``vmap_jit__pallas_accumulate__.4``), and the operation's text or
    metadata (``texts``) holds ``tpu_custom_call`` or ``pallas_call``.
    The kernel is the instruction's name without its numeric suffix."""
    base = re.sub(r"\.\d+$", "", op_name(name))
    texts = f"{name} {texts}"
    if ("pallas_call" in texts or "tpu_custom_call" in texts
            or "pallas" in base or base in _KNOWN):
        return base
    return ""


def _op(event) -> Op:
    texts = " ".join(str(v) for k, v in event.stats
                     if k in ("long_name", "tf_op", "hlo_category"))
    return Op(op_name(event.name), kernel_of(event.name, texts),
              int(event.start_ns), int(event.end_ns))


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: Dict[str, List[Op]] = {}
    spans: List[Tuple[str, int, int]] = []
    host_ops: List[Op] = []
    on_tpu = any(p.name.startswith("/device:TPU:") for p in data.planes)
    for plane in data.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name != _OPS_LINE:
                    continue
                devices[plane.name] = [_op(e) for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        spans.append((e.name, int(e.start_ns),
                                      int(e.end_ns)))
                    elif not on_tpu and _stat(e, "hlo_op") is not None:
                        host_ops.append(_op(e))
    if not devices and host_ops:
        # XLA's CPU backend runs its operations on host threads: the
        # rehearsal of a traced run on the CPU reads them as its device.
        devices["/host:CPU"] = sorted(host_ops, key=lambda o: o.start)
    return Trace(devices=devices, spans=spans)


def merge(intervals: List[Interval], lo: int, hi: int) -> List[Interval]:
    """Union of ``intervals`` clipped to [lo, hi], sorted, disjoint."""
    out: List[Interval] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], e))
        else:
            out.append((s, e))
    return out


def busy_ns(ops: List[Op], window: Interval) -> int:
    return sum(e - s for s, e in merge([(o.start, o.end) for o in ops],
                                       *window))


def idle_gaps(ops: List[Op], spans, window: Interval
              ) -> List[Tuple[str, int]]:
    """Each idle gap of the window with the host span it is charged to:
    of the spans that cover the gap's midpoint, the one that started
    last (host spans nest, so that is the innermost)."""
    lo, hi = window
    busy = merge([(o.start, o.end) for o in ops], lo, hi)
    gaps, t = [], lo
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = e
    if t < hi:
        gaps.append((t, hi))
    inner = sorted((s, e, n) for n, s, e in spans if n != WINDOW_SPAN)
    active: list = []          # heap of (-start, end, name)
    k = 0
    out = []
    for s, e in gaps:          # midpoints ascend, so ended spans go for good
        mid = (s + e) // 2
        while k < len(inner) and inner[k][0] <= mid:
            heapq.heappush(active, (-inner[k][0], inner[k][1], inner[k][2]))
            k += 1
        while active and active[0][1] <= mid:
            heapq.heappop(active)
        out.append((active[0][2] if active else "host:none", e - s))
    return out


def breakdown(trace: Trace, device: str, window: Interval,
              top: int = 10) -> dict:
    """The operations that took most device time and the idle time by
    what the host was doing, each as ``[[name, seconds], ...]``."""
    ops = trace.devices[device]
    lo, hi = window
    per_op: Dict[str, int] = {}
    for o in ops:
        d = min(o.end, hi) - max(o.start, lo)
        if d > 0:
            per_op[o.name] = per_op.get(o.name, 0) + d
    per_gap: Dict[str, int] = {}
    for name, d in idle_gaps(ops, trace.spans, window):
        per_gap[name] = per_gap.get(name, 0) + d
    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
    return {"device_ops": [[n, v / 1e9] for n, v in rank(per_op)],
            "idle_gaps": [[n, v / 1e9] for n, v in rank(per_gap)]}


def save(trace: Trace, path: str) -> None:
    with open(path, "w") as f:
        json.dump(trace.to_json(), f)

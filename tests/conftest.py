"""Test bootstrap: put ``src/`` (and the repo root, for ``benchmarks.*``)
on ``sys.path`` so ``python -m pytest -q`` works from a clean checkout
without the ``PYTHONPATH=src`` incantation; and the ``capture`` fixture,
a CPU profiler capture read back with ``jax.profiler.ProfileData``."""

import dataclasses
import glob
import os
import sys
from typing import Dict, List

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (os.path.join(_ROOT, "src"), _ROOT):
    if path not in sys.path:
        sys.path.insert(0, path)


@dataclasses.dataclass
class HostEvent:
    name: str
    start_ns: float
    end_ns: float
    thread: int          # the trace line (one per host thread)
    stats: Dict[str, object]


class Capture:
    """A profiler capture into ``directory``; on exit, ``events`` holds
    the host events it recorded, read back with ``ProfileData``."""

    def __init__(self, directory: str):
        self.directory = directory
        self.events: List[HostEvent] = []

    def __enter__(self):
        import jax
        jax.profiler.start_trace(self.directory)
        return self

    def __exit__(self, *exc):
        import jax
        from jax.profiler import ProfileData
        jax.profiler.stop_trace()
        path, = glob.glob(f"{self.directory}/**/*.xplane.pb",
                          recursive=True)
        data = ProfileData.from_file(path)
        for plane in data.planes:
            if not plane.name.startswith("/host:"):
                continue
            for i, line in enumerate(plane.lines):
                for e in line.events:
                    self.events.append(HostEvent(
                        e.name, e.start_ns, e.end_ns, i, dict(e.stats)))
        return False

    def named(self, name: str) -> List[HostEvent]:
        return [e for e in self.events if e.name == name]


@pytest.fixture()
def capture(tmp_path):
    """``with capture() as cap: ...`` records a CPU profiler capture;
    ``cap.events`` are its host events."""
    count = iter(range(1 << 30))
    return lambda: Capture(str(tmp_path / f"capture{next(count)}"))

"""What every traffic generator shares.

A mix file (``mixes/<traffic>.json``) holds only parameters; its
``loop`` key names the generator, ``loop/<loop>.py``, whose ``LOOP`` is
a subclass of :class:`Loop`.  Each generator builds its inputs from the
seed in ``setup`` (warming every shape it will use), drives the system
for the window in ``window`` and returns the end-to-end values, and
keeps a seeded sample of what the timed path produced for ``check`` to
compare with the plain reference once the window has closed;
``substitute`` puts the reference in the program's place for the
control.  The window closes once all the work it dispatched is done.
"""

from __future__ import annotations

import contextlib
from typing import Dict, List

import numpy as np


class Spans:
    """Host spans around calls into the system's layers: profiler
    annotations when the run is traced, nothing otherwise."""

    def __init__(self, on: bool):
        self.on = on
        if on:
            import jax
            self._annotation = jax.profiler.TraceAnnotation

    def __call__(self, name: str):
        if self.on:
            return self._annotation("cb." + name)
        return contextlib.nullcontext()


class Reservoir:
    """A uniform sample of at most ``size`` items from a stream, drawn
    from the seed."""

    def __init__(self, size: int, rng: np.random.Generator):
        self.size, self.rng, self.seen, self.items = size, rng, 0, []

    def offer(self, item) -> None:
        self.seen += 1
        if len(self.items) < self.size:
            self.items.append(item)
        else:
            j = int(self.rng.integers(self.seen))
            if j < self.size:
                self.items[j] = item


def _rngs(seed: int):
    """Independent generators for inputs, order and sampling."""
    ss = np.random.SeedSequence(seed)
    return [np.random.default_rng(s) for s in ss.spawn(3)]


class Loop:
    """What every generator reports besides its end-to-end values: the
    units attempted and failed in the window, the logical shape of each
    call into the system (``calls``) and counters, for the per-layer
    readers."""

    def __init__(self):
        self.calls: List[tuple] = []
        self.counters: Dict[str, float] = {}
        self.attempted = 0
        self.failed = 0

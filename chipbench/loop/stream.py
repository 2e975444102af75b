"""The ``stream`` loop: a closed loop of ``run_streaming`` over a pool of
seeded host batches, cycled until the window ends (image pipelines).
Its end-to-end value is ``mpix_per_s``."""

from __future__ import annotations

import time
from typing import Dict

import numpy as np

from chipbench import compare
from chipbench.loops import Loop, Reservoir, Spans, _rngs


class StreamLoop(Loop):
    """``run_streaming`` over ``pool`` seeded (batch, size, size) uint8
    batches, ``depth`` of them in flight, ``chunk`` batches per call (so
    the window retains at most ``chunk`` outputs besides the sample)."""

    def __init__(self, system, mix: dict, seed: int, spans: Spans,
                 name: str):
        super().__init__()
        self.pipe, self.mix, self.spans = system, mix, spans
        rin, _, rsample = _rngs(seed)
        shape = (mix["batch"], mix["size"], mix["size"])
        self.pool = [rin.integers(0, 256, shape, dtype=np.uint8)
                     for _ in range(mix["pool"])]
        self.sample = Reservoir(mix["sample"], rsample)

    def setup(self) -> None:
        from repro.imgproc import run_streaming
        res = run_streaming(self.pipe, self.pool[:self.mix["depth"] + 1],
                            depth=self.mix["depth"])
        if any(o is None for o in res.outputs):
            raise RuntimeError("a warm-up batch produced no output")

    def window(self, seconds: float) -> Dict[str, float]:
        from repro.imgproc import run_streaming
        depth, chunk = self.mix["depth"], self.mix["chunk"]
        n_pool = len(self.pool)
        px = int(self.pool[0].size)

        def call(batch):
            with self.spans("plan.call"):
                return self.pipe(batch)

        t0 = time.perf_counter()
        t_end = t0 + seconds
        index = 0

        def batches(first: int):
            for j in range(chunk):
                if time.perf_counter() >= t_end:
                    return
                yield self.pool[(first + j) % n_pool]

        # Batches are dispatched until ``seconds`` have passed; the
        # window closes when the last of their outputs is on the host.
        failed = 0
        while time.perf_counter() < t_end:
            with self.spans("stream.run_streaming"):
                res = run_streaming(call, batches(index), depth=depth)
            for j, out in enumerate(res.outputs):
                failed += out is None
                self.sample.offer(((index + j) % n_pool, out))
            index += len(res.outputs)
        elapsed = time.perf_counter() - t0
        self.attempted, self.failed = index, failed
        self.calls = [self.pool[0].shape] * index
        self.counters = {"batches": index, "window_s": elapsed}
        return {"mpix_per_s": (index - failed) * px / elapsed / 1e6}

    def check(self, ref, cfg) -> Dict[str, tuple]:
        bad = compared = 0
        want: Dict[int, np.ndarray] = {}
        for i, out in self.sample.items:
            if i not in want:
                want[i] = np.asarray(ref.reference(self.pool[i], cfg))
            b, c = compare.mismatches(out, want[i])
            bad, compared = bad + b, compared + c
        return {"bad_px": (bad, 0), "compared_px": (compared, None)}

    def substitute(self, ref, cfg) -> None:
        """The control: the reference under ``cfg`` in the program's
        place, for the same sampled inputs."""
        self.sample.items = [(i, np.asarray(ref.reference(self.pool[i],
                                                          cfg)))
                             for i, _ in self.sample.items]


LOOP = StreamLoop

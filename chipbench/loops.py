"""The general traffic generators: one per kind of loop a mix names.

A mix file (``mixes/<traffic>.json``) holds only parameters; its
``loop`` key picks one of the generators below.  Each generator builds
its inputs from the seed in ``setup`` (warming every shape it will
use), drives the system for the window in ``window`` and returns the
end-to-end values, and keeps a seeded sample of what the timed path
produced for ``check`` to compare with the plain reference once the
window has closed.

* ``stream``: closed loop of ``run_streaming`` over a pool of seeded
  host batches, cycled until the window ends (image pipelines).
* ``gemm``: closed loop over fixed GEMM shapes whose operands already
  sit on the device (MAC engines).
"""

from __future__ import annotations

import collections
import contextlib
import time
from typing import Callable, Dict, List

import numpy as np

from chipbench import compare


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


# ----------------------------------------------------------------- stream --

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


# ------------------------------------------------------------------- gemm --

class GemmLoop(Loop):
    """A closed loop over ``shapes`` ([M, K, N], in turn), at most
    ``ahead`` GEMMs in flight; operands are made on the device from the
    seed in one jitted call."""

    def __init__(self, system, mix: dict, seed: int, spans: Spans,
                 name: str):
        super().__init__()
        self.matmul, self.mix, self.spans = system, mix, spans
        self.shapes = [tuple(s) for s in mix["shapes"]]
        words = np.random.SeedSequence(seed).generate_state(2)
        self.key = (int(words[0]), int(words[1]))
        _, _, rsample = _rngs(seed)
        self.samples = [Reservoir(1, rsample) for _ in self.shapes]

    def _operands(self):
        import jax
        import jax.numpy as jnp

        def make(key):
            keys = jax.random.split(key, 2 * len(self.shapes))
            out = []
            for i, (m, k, n) in enumerate(self.shapes):
                a = jax.random.randint(keys[2 * i], (m, k), -128, 128,
                                       jnp.int32).astype(jnp.int8)
                b = jax.random.randint(keys[2 * i + 1], (k, n), -128, 128,
                                       jnp.int32).astype(jnp.int8)
                out.append((a, b))
            return out

        key = jax.random.fold_in(jax.random.key(self.key[0]), self.key[1])
        return jax.block_until_ready(jax.jit(make)(key))

    def setup(self) -> None:
        self.operands = self._operands()
        for a, b in self.operands:
            self.matmul(a, b).block_until_ready()

    def window(self, seconds: float) -> Dict[str, float]:
        # GEMMs are dispatched until ``seconds`` have passed, with at
        # most ``ahead`` of them in flight beyond the one waited for, so
        # the chip stays fed while the host stands still.  Then nothing
        # more is sent, and the window closes when every GEMM sent is
        # done: all of that work counts, over all of that time.
        ahead = self.mix["ahead"]
        pending: collections.deque = collections.deque()
        t0 = time.perf_counter()
        t_end = t0 + seconds
        macs = 0
        calls = []
        i = 0
        while time.perf_counter() < t_end:
            s = i % len(self.shapes)
            a, b = self.operands[s]
            with self.spans("engine.matmul"):
                out = self.matmul(a, b)
            pending.append(out)
            m, k, n = self.shapes[s]
            macs += m * k * n
            self.samples[s].offer(out)
            calls.append(self.shapes[s])
            i += 1
            if len(pending) > ahead:
                with self.spans("engine.wait"):
                    pending.popleft().block_until_ready()
        with self.spans("engine.wait"):
            for out in pending:
                out.block_until_ready()
        elapsed = time.perf_counter() - t0
        self.attempted = i
        self.calls = calls
        self.counters = {"gemms": i, "window_s": elapsed}
        return {"gmac_per_s": macs / elapsed / 1e9}

    def check(self, ref, cfg) -> Dict[str, tuple]:
        bad = compared = 0
        for (a, b), sample in zip(self.operands, self.samples):
            for out in sample.items:
                b_, c = compare.mismatches_device(out, ref.reference(a, b,
                                                                     cfg))
                bad, compared = bad + b_, compared + c
        return {"bad_el": (bad, 0), "compared_el": (compared, None)}

    def substitute(self, ref, cfg) -> None:
        for (a, b), sample in zip(self.operands, self.samples):
            sample.items = [ref.reference(a, b, cfg) for _ in sample.items]


LOOPS: Dict[str, Callable] = {"stream": StreamLoop, "gemm": GemmLoop}

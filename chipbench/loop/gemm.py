"""The ``gemm`` loop: a closed loop over fixed GEMM shapes whose operands
already sit on the device (MAC engines).  Its end-to-end value is
``gmac_per_s``."""

from __future__ import annotations

import collections
import time
from typing import Dict

import numpy as np

from chipbench import compare
from chipbench.loops import Loop, Reservoir, Spans, _rngs


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


LOOP = GemmLoop

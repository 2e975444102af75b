"""A closed-loop toy decode: ``batch`` sequences from seeded first
tokens, each step's output fed back in, at most ``ahead`` steps in
flight.  Its end-to-end value is ``tok_per_s``: one token per sequence
for every step of the window, over the window's seconds."""

from __future__ import annotations

import collections
import time
from typing import Dict

import numpy as np

from chipbench import compare
from chipbench.loops import Loop, Reservoir, Spans, _rngs


class ToyDecodeLoop(Loop):

    def __init__(self, system, mix: dict, seed: int, spans: Spans,
                 name: str):
        super().__init__()
        self.step, self.mix, self.spans = system, mix, spans
        rin, _, rsample = _rngs(seed)
        self.first = rin.integers(0, mix["first_below"], mix["batch"],
                                  dtype=np.int32)
        self.sample = Reservoir(mix["sample"], rsample)

    def setup(self) -> None:
        import jax.numpy as jnp
        self.tokens = jnp.asarray(self.first)
        self.step(self.tokens).block_until_ready()

    def window(self, seconds: float) -> Dict[str, float]:
        pending: collections.deque = collections.deque()
        tokens = self.tokens
        t0 = time.perf_counter()
        t_end = t0 + seconds
        steps = 0
        while time.perf_counter() < t_end:
            with self.spans("decode.step"):
                tokens = self.step(tokens)
            steps += 1
            self.sample.offer((steps, tokens))
            pending.append(tokens)
            if len(pending) > self.mix["ahead"]:
                pending.popleft().block_until_ready()
        tokens.block_until_ready()
        elapsed = time.perf_counter() - t0
        batch = len(self.first)
        self.attempted = steps * batch
        self.calls = [(batch,)] * steps
        self.counters = {"steps": steps, "window_s": elapsed}
        return {"tok_per_s": steps * batch / elapsed}

    def check(self, ref, cfg) -> Dict[str, tuple]:
        bad = compared = 0
        for steps, out in self.sample.items:
            b, c = compare.mismatches(out, ref.reference(self.first, steps,
                                                         cfg))
            bad, compared = bad + b, compared + c
        return {"bad_tok": (bad, 0), "compared_tok": (compared, None)}

    def substitute(self, ref, cfg) -> None:
        self.sample.items = [(steps, ref.reference(self.first, steps, cfg))
                             for steps, _ in self.sample.items]


LOOP = ToyDecodeLoop

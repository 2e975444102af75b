"""Batched serving: greedy/sampled generation on top of prefill/decode.

Host-side driver used by examples and tests; the jitted step functions
come from launch/steps.py (the same ones the dry-run lowers at scale).
"""

from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models.config import ModelConfig


def generate(params, cfg: ModelConfig, batch: Dict, max_new_tokens: int,
             *, temperature: float = 0.0, seed: int = 0,
             ctx_budget: Optional[int] = None):
    """batch: {"tokens": (B, S_prompt)} (+"vision").  Returns (B, S+new)."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    ctx = ctx_budget or (s + max_new_tokens)
    prefill = make_prefill_step(cfg, ctx)
    decode = make_decode_step(cfg)
    logits, cache, _ = prefill(params, batch)
    out = [tokens]
    rng = jax.random.key(seed)
    last = None
    for i in range(max_new_tokens):
        if temperature <= 0:
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        else:
            rng, sub = jax.random.split(rng)
            nxt = jax.random.categorical(
                sub, logits[:, -1].astype(jnp.float32) / temperature, -1
            ).astype(jnp.int32)
        nxt = nxt[:, None]
        out.append(nxt)
        if i == max_new_tokens - 1:
            break
        logits, cache, _ = decode(params, nxt, jnp.int32(s + i), cache)
    return jnp.concatenate(out, axis=1)


def throughput_report(n_tokens: int, seconds: float, batch: int) -> str:
    tps = n_tokens * batch / max(seconds, 1e-9)
    return f"{tps:,.0f} tok/s ({n_tokens} steps x batch {batch} in {seconds:.2f}s)"

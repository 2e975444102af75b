"""jit-able step functions: train_step / prefill_step / decode_step.

These are the units the launcher lowers; the dry-run compiles them for the
production meshes and the train loop executes them on the host mesh.
"""

from __future__ import annotations

import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro import obs
from repro.models import transformer as T
from repro.models.config import ModelConfig
from repro.optim import adamw

State = Dict[str, Any]


def init_state(rng, cfg: ModelConfig, opt_cfg: adamw.AdamWConfig) -> State:
    params = T.init_params(rng, cfg)
    return {"params": params, "opt": adamw.init(params),
            "step": jnp.zeros((), jnp.int32)}


def make_train_step(cfg: ModelConfig, opt_cfg: adamw.AdamWConfig,
                    batch_axes=None, grad_transform=None,
                    microbatches: int = 1, mesh=None):
    """Fused forward/backward/update step.

    microbatches > 1 = gradient accumulation: the global batch is split
    along dim 0 and scanned sequentially, dividing activation memory by
    the microbatch count at identical math (memory knob for cells whose
    temp footprint exceeds HBM without paying SP collective costs)."""

    def grads_of(params, batch):
        def lf(p):
            return T.loss_fn(p, cfg, batch, batch_axes=batch_axes, mesh=mesh)

        return jax.value_and_grad(lf, has_aux=True)(params)

    def train_step(state: State, batch):
        if microbatches == 1:
            (loss, parts), grads = grads_of(state["params"], batch)
        else:
            mb = jax.tree.map(
                lambda x: x.reshape(microbatches, x.shape[0] // microbatches,
                                    *x.shape[1:]), batch)

            def body(acc, micro):
                (l, pa), g = grads_of(state["params"], micro)
                return jax.tree.map(jnp.add, acc, (g, l, pa)), None

            zeros = (jax.tree.map(jnp.zeros_like, state["params"]),
                     jnp.zeros(()), {"ce": jnp.zeros(()),
                                     "aux": jnp.zeros(())})
            (grads, loss, parts), _ = jax.lax.scan(body, zeros, mb)
            grads = jax.tree.map(lambda g: g / microbatches, grads)
            loss = loss / microbatches
            parts = jax.tree.map(lambda x: x / microbatches, parts)
        if grad_transform is not None:
            grads = grad_transform(grads)
        new_params, new_opt, om = adamw.update(
            opt_cfg, grads, state["opt"], state["params"])
        metrics = {"loss": loss, **parts, **om}
        return {"params": new_params, "opt": new_opt,
                "step": state["step"] + 1}, metrics

    return train_step


def _spanned(name: str, fn):
    """``fn`` called inside the ``repro.obs`` span ``name``."""
    @functools.wraps(fn)
    def call(*args):
        with obs.span(name):
            return fn(*args)

    return call


def make_prefill_step(cfg: ModelConfig, ctx_len: int, batch_axes=None):
    """``(params, batch) -> (logits, cache, stats)``, jitted, each call in
    the span ``model:prefill``."""
    def prefill_step(params, batch):
        b = (batch["tokens"] if "tokens" in batch else batch["frames"]).shape[0]
        cache = T.init_cache(cfg, b, ctx_len)
        return T.forward(params, cfg, batch, mode="prefill", cache=cache,
                         batch_axes=batch_axes)

    return _spanned("model:prefill", jax.jit(prefill_step))


def make_decode_step(cfg: ModelConfig, batch_axes=None, taps=False):
    """``(params, tokens (B, 1), pos () or (B,), cache) -> (logits, cache,
    stats)``, jitted with the cache donated (updated in place), each call
    in the span ``model:decode``.  ``taps``: stats also holds the blocks'
    intermediates (``transformer.forward``)."""
    def decode_step(params, tokens, pos, cache):
        return T.forward(params, cfg, {"tokens": tokens}, mode="decode",
                         cache=cache, pos=pos, batch_axes=batch_axes,
                         taps=taps)

    return _spanned("model:decode", jax.jit(decode_step, donate_argnums=3))


def state_shapes(cfg: ModelConfig, opt_cfg: adamw.AdamWConfig, seed=0):
    """eval_shape of the full train state — NO allocation."""
    return jax.eval_shape(
        functools.partial(init_state, cfg=cfg, opt_cfg=opt_cfg),
        jax.random.key(seed))


def params_shapes(cfg: ModelConfig, seed=0):
    return jax.eval_shape(functools.partial(T.init_params, cfg=cfg),
                          jax.random.key(seed))


def cache_shapes(cfg: ModelConfig, batch: int, ctx_len: int):
    return jax.eval_shape(
        functools.partial(T.init_cache, cfg, batch, ctx_len))

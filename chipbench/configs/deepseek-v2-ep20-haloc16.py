"""Plain reference of ``deepseek-v2-ep20-haloc16``: DeepSeek-V2's forward
pass over one token sequence, in float32, with no cache and no batching,
for this chip's share of the model, and its MoE layer and residual add
on their own.

Written from the published ``config.json`` and ``modeling_deepseek.py``
of https://huggingface.co/deepseek-ai/DeepSeek-V2 (arXiv:2405.04434).
The weights are read by their published names and in their published
layout (``torch.nn.Linear``: ``(out_features, in_features)``), e.g.
``model.layers.3.self_attn.kv_b_proj.weight`` or
``model.layers.3.mlp.experts.5.down_proj.weight``:

* MLA in its decompressed form: ``q = RMSNorm(x Wq_a) Wq_b`` split into
  128-dim "nope" and 64-dim rope parts per head; ``[c_kv, k_rope] =
  x Wkv_a`` with ``c_kv`` RMS-normed and expanded by ``Wkv_b`` into
  per-head ``k_nope`` and ``v``; one rope key shared by the heads; causal
  softmax attention at scale ``192^-0.5 * mscale(40, 0.707)^2``.
* YaRN rope (DeepseekV2YarnRotaryEmbedding): the frequencies ``f`` and
  ``f / factor`` blended by a linear ramp between the correction
  dimensions of ``beta_fast`` and ``beta_slow`` rotations over the
  original 4096 positions; cos and sin scaled by ``mscale(factor,
  mscale) / mscale(factor, mscale_all_dim)``.
* Layer 0's FFN is a SwiGLU of width 12288; layers 1-4 are MoE layers:
  softmax over all 160 router outputs, ``group_limited_greedy`` (8
  groups, each scored by its best expert; the top-6 experts inside the
  3 best groups), gates times ``routed_scaling_factor`` 16
  (``norm_topk_prob`` false), plus 2 shared experts (one SwiGLU of width
  3072).

Departures from the published model, each deliberate:

* This chip's share (the configuration's ``deployment``): of the 160
  routed experts only experts 0-7 are computed, for the tokens routed
  to them, with no drop; what the others would add is left out, and
  that partial result goes on to the next layer.  The vocabulary is its
  first eighth; only layers 0-4 are present.
* Every residual add (two per layer) is the configured adder on Q8.8
  values: each operand is rounded to the nearest multiple of 1/256
  (half to even), saturated to 16 bits, added by ``refcore.haloc_add``
  (n=16, m=8, k=4) and sign-extended.  The control puts the exact
  16-bit add there.
* The published code de-interleaves the rope dimensions before rotating
  (``view(d/2, 2).transpose``); here the two halves are rotated as they
  lie.  With seeded weights that is a fixed relabelling of the rope
  rows of ``q_b_proj`` and ``kv_a_proj_with_mqa``.
* Weights are seeded bfloat16 values, read as float32.
* Attention is computed in blocks of queries so that about 16k
  positions fit on one chip.
"""

from __future__ import annotations

import functools
import json
import math

import jax
import jax.numpy as jnp

from chipbench import refcore

#: Queries per attention block.
Q_BLOCK = 128


def _mscale(scale, m):
    return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0


def yarn_tables(positions, dim, base, rope):
    """cos, sin of shape (len(positions), dim // 2)."""
    factor = rope["factor"]
    orig = rope["original_max_position_embeddings"]

    def corr(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr(rope["beta_fast"])), 0)
    high = min(math.ceil(corr(rope["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    freq = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    ramp = jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low)
                    / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv = freq / factor * (1 - mask) + freq * mask
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    s = _mscale(factor, rope["mscale"]) / _mscale(factor,
                                                  rope["mscale_all_dim"])
    return jnp.cos(ang) * s, jnp.sin(ang) * s


def _rope(x, cos, sin):
    """x (L, ..., d): rotate the two halves of the last axis."""
    while cos.ndim < x.ndim:
        cos, sin = cos[:, None], sin[:, None]
    a, b = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def _norm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _lin(x, w):
    """``torch.nn.Linear`` without bias: x @ w.T."""
    return x @ w.T


def residual_add(x, y, adder):
    """The configured adder on the Q8.8 values of x and y, as float32."""
    n, f = adder["n_bits"], adder["frac_bits"]
    lo, hi = -(1 << (n - 1)), (1 << (n - 1)) - 1

    def pattern(v):
        q = jnp.clip(jnp.round(v * (1 << f)), lo, hi).astype(jnp.int32)
        return refcore.to_pattern(q, n)

    add = refcore.ADDERS[adder["kind"]]
    s = add(pattern(x), pattern(y), n, adder["lsm_bits"], adder["const_bits"])
    return refcore.to_signed(s, n).astype(jnp.float32) / (1 << f)


def _swiglu(w, x):
    return _lin(jax.nn.silu(_lin(x, w["gate_proj"])) * _lin(x, w["up_proj"]),
                w["down_proj"])


def _mla(w, x, cfg):
    """The attention output at every position of x (L, D)."""
    length = x.shape[0]
    h = cfg["num_attention_heads"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    r = cfg["kv_lora_rank"]
    eps = cfg["rms_norm_eps"]
    rope = cfg["rope_scaling"]
    cos, sin = yarn_tables(jnp.arange(length), dr, float(cfg["rope_theta"]),
                           rope)
    q = _lin(_norm(_lin(x, w["q_a_proj"]), w["q_a_layernorm"], eps),
             w["q_b_proj"]).reshape(length, h, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], cos, sin)], -1)
    ckv = _lin(x, w["kv_a_proj_with_mqa"])
    c = _norm(ckv[:, :r], w["kv_a_layernorm"], eps)
    k_rope = _rope(ckv[:, r:], cos, sin)
    kv = _lin(c, w["kv_b_proj"]).reshape(length, h, dn + dv)
    k = jnp.concatenate([kv[..., :dn], jnp.broadcast_to(
        k_rope[:, None], (length, h, dr))], -1)
    v = kv[..., dn:]
    scale = (dn + dr) ** -0.5 * _mscale(rope["factor"],
                                        rope["mscale_all_dim"]) ** 2
    size = min(Q_BLOCK, length)
    if length % size:
        raise ValueError(f"{length} positions are not blocks of {size}")

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * size, size)
        s = jnp.einsum("qhd,khd->hqk", qb, k) * scale
        qpos = i * size + jnp.arange(size)
        s = jnp.where(jnp.arange(length)[None, None] <= qpos[None, :, None],
                      s, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(block, jnp.arange(length // size)).reshape(
        length, h * dv)
    return _lin(out, w["o_proj"])


def _route(h, w, cfg):
    """(gates, ids) of the top experts: softmax over every router output,
    the best groups, the top-k inside them, scaled or renormalised."""
    probs = jax.nn.softmax(_lin(h, w), -1)
    e = probs.shape[-1]
    g = cfg["n_group"]
    if cfg["topk_method"] == "group_limited_greedy":
        best = probs.reshape(-1, g, e // g).max(-1)
        _, top = jax.lax.top_k(best, cfg["topk_group"])
        keep = jnp.zeros_like(best).at[jnp.arange(best.shape[0])[:, None],
                                       top].set(1.0)
        probs = probs * jnp.repeat(keep, e // g, axis=-1)
    gates, ids = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        gates = gates / gates.sum(-1, keepdims=True)
    else:
        gates = gates * cfg["routed_scaling_factor"]
    return gates, ids


def _moe(w, h, cfg, gates=None, ids=None):
    """The MoE output for the normed input h (N, D): the held experts for
    the tokens routed to them (by the reference's own routing, or by
    ``gates``, ``ids`` where given) and the shared experts."""
    if gates is None:
        gates, ids = _route(h, w["gate"], cfg)
    first, _ = cfg["deployment"]["held"]
    out = _swiglu(w["shared_experts"], h)
    for j, expert in enumerate(w["experts"]):
        g = jnp.sum(jnp.where(ids == first + j, gates, 0.0), -1)
        out = out + g[:, None] * _swiglu(expert, h)
    return out, gates, ids


def _layer_weights(weights, i: int, cfg) -> dict:
    """Layer i's weights by their published names, as float32."""
    pre = f"model.layers.{i}."

    def get(name):
        return jnp.asarray(weights[pre + name], jnp.float32)

    def swiglu(prefix):
        return {k: get(f"{prefix}.{k}.weight")
                for k in ("gate_proj", "up_proj", "down_proj")}

    w = {"input_layernorm": get("input_layernorm.weight"),
         "post_attention_layernorm": get("post_attention_layernorm.weight"),
         "attn": {k: get(f"self_attn.{k}.weight") for k in (
             "q_a_proj", "q_a_layernorm", "q_b_proj", "kv_a_proj_with_mqa",
             "kv_a_layernorm", "kv_b_proj", "o_proj")}}
    if i < cfg["first_k_dense_replace"]:
        w["mlp"] = swiglu("mlp")
    else:
        first, count = cfg["deployment"]["held"]
        w["mlp"] = {"gate": get("mlp.gate.weight"),
                    "shared_experts": swiglu("mlp.shared_experts"),
                    "experts": [swiglu(f"mlp.experts.{e}")
                                for e in range(first, first + count)]}
    return w


def _frozen(cfg) -> str:
    return json.dumps(cfg, sort_keys=True)


@functools.partial(jax.jit, static_argnames=("frozen",))
def _block(w, x, frozen):
    """One decoder layer over x (L, D): (its result, the attention
    output)."""
    cfg = json.loads(frozen)
    eps = cfg["rms_norm_eps"]
    with jax.default_matmul_precision("highest"):
        mix = _mla(w["attn"], _norm(x, w["input_layernorm"], eps), cfg)
        x = residual_add(x, mix, cfg["adder"])
        h = _norm(x, w["post_attention_layernorm"], eps)
        if "gate" in w["mlp"]:
            y = _moe(w["mlp"], h, cfg)[0]
        else:
            y = _swiglu(w["mlp"], h)
        return residual_add(x, y, cfg["adder"]), mix


@functools.partial(jax.jit, static_argnames=("frozen",))
def _head(norm, head, x, frozen):
    cfg = json.loads(frozen)
    with jax.default_matmul_precision("highest"):
        return _lin(_norm(x, norm, cfg["rms_norm_eps"]), head)


def reference(weights, tokens, length: int, cfg: dict) -> dict:
    """Over ``tokens[:length]``: ``logits`` (vocab,) at the last position,
    and ``attn0`` (hidden,), layer 0's attention output there (before its
    residual add).  ``weights`` maps published names to arrays.
    ``tokens`` may run past ``length`` (a fixed padded shape, one
    compile); causal attention keeps the tail out."""
    frozen = _frozen(cfg)
    tokens = jnp.asarray(tokens, jnp.int32)
    last = length - 1
    x = jnp.asarray(weights["model.embed_tokens.weight"],
                    jnp.float32)[tokens]
    for i in range(cfg["num_hidden_layers"]):
        x, mix = _block(_layer_weights(weights, i, cfg), x, frozen)
        if i == 0:
            attn0 = mix[last]
    logits = _head(jnp.asarray(weights["model.norm.weight"], jnp.float32),
                   jnp.asarray(weights["lm_head.weight"], jnp.float32),
                   x[last], frozen)
    return {"logits": logits, "attn0": attn0}


@functools.partial(jax.jit, static_argnames=("frozen",))
def _moe_layer(w, x, gates, ids, frozen):
    cfg = json.loads(frozen)
    with jax.default_matmul_precision("highest"):
        h = _norm(x, w["post_attention_layernorm"], cfg["rms_norm_eps"])
        mine = _route(h, w["mlp"]["gate"], cfg)
        return _moe(w["mlp"], h, cfg, gates, ids)[0], mine


def moe_layer(weights, i: int, x, gates, ids, cfg: dict):
    """Layer i's MoE on rows x (N, D) of the residual stream after the
    attention's add: (the output with the given routing ``gates``,
    ``ids`` (N, k), and the reference's own (gates, ids))."""
    return _moe_layer(_layer_weights(weights, i, cfg),
                      jnp.asarray(x, jnp.float32),
                      jnp.asarray(gates, jnp.float32),
                      jnp.asarray(ids, jnp.int32), _frozen(cfg))

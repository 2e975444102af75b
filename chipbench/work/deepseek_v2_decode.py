"""Logical work of one DeepSeek-V2 decode step on this chip's share,
from its shape (batch, live context tokens summed over the batch) and
the configuration.

Operations: two per weight that a token multiplies, for the batch's
tokens: MLA's projections (``Wkv_b`` absorbed into the query and the
output costs what it holds), the dense layer's FFN, each MoE layer's
router, shared experts and the routed experts it computes (each token
reaches ``num_experts_per_tok * held / routed`` of them on average), and
the head; plus absorbed attention, ``2 * heads * (kv_lora + rope)`` for
the scores and ``2 * heads * kv_lora`` for the context per cached token
and layer.

Bytes: every weight read once (of the embedding only the batch's rows)
and the live latent cache read once.

:func:`step_macs` is the multiply-accumulates of one step at the shape
the step is compiled for, attention over every slot of the cache.
"""

from __future__ import annotations

_BYTES = {"bfloat16": 2}


def _mla_params(c):
    h, d = c["num_attention_heads"], c["hidden_size"]
    dq = c["qk_nope_head_dim"] + c["qk_rope_head_dim"]
    r, dr = c["kv_lora_rank"], c["qk_rope_head_dim"]
    return (d * c["q_lora_rank"] + c["q_lora_rank"] * h * dq + d * (r + dr)
            + r * h * (c["qk_nope_head_dim"] + c["v_head_dim"])
            + h * c["v_head_dim"] * d)


def work(shape, cfg):
    """(operations, bytes) of one decode step."""
    batch, live = shape
    c = cfg
    d = c["hidden_size"]
    layers = c["num_hidden_layers"]
    dense = c["first_k_dense_replace"]
    moe = layers - dense
    expert = 3 * d * c["moe_intermediate_size"]
    routed_total = c["deployment"]["n_routed_experts"]
    held = c["n_routed_experts"]
    mla = _mla_params(c)
    ffn = 3 * d * c["intermediate_size"]
    router = d * routed_total
    shared = c["n_shared_experts"] * expert
    head = d * c["vocab_size"]
    per_token = (layers * mla + dense * ffn
                 + moe * (router + shared
                          + c["num_experts_per_tok"] * held / routed_total
                          * expert)
                 + head)
    h, r, dr = c["num_attention_heads"], c["kv_lora_rank"], \
        c["qk_rope_head_dim"]
    attention = 2 * h * (r + dr + r) * live * layers
    ops = 2 * batch * per_token + attention
    weights = (layers * mla + dense * ffn
               + moe * (router + shared + held * expert) + head
               + batch * d)
    cache = live * layers * (r + dr) * _BYTES[c["cache_dtype"]]
    return ops, weights * _BYTES[c["dtype"]] + cache


def step_macs(batch, ctx, cfg):
    """Multiply-accumulates of one decode step at its compiled shape:
    :func:`work`'s operations over two with every row attending all
    ``ctx`` slots of its cache.  It does not depend on the rows' lengths,
    so it is the same for every step and seed of a cell."""
    return work((batch, batch * ctx), cfg)[0] / 2

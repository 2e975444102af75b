"""Mixture-of-Experts MLP: one router over every expert, and the part of
the result that the experts held here give.

Routing (:func:`route`) is the softmax over all ``num_experts`` router
outputs, then either a plain top-k (``"greedy"``) or DeepSeek-V2's
``"group_limited_greedy"`` (the top-k inside the ``topk_group`` groups
whose best expert scores highest); the k gates are renormalised
(``norm_topk_prob``) or scaled by ``routed_scaling_factor``.

A layer holds the experts ``held = (first, count)`` (all by default): its
parameter stacks are ``(count, D, F)``, and it computes only those
experts, for the (token, slot) pairs routed to them.  On one chip that
is a share of an expert-parallel deployment; under ``shard_map`` each
rank is the share ``first = rank * count`` and one ``psum`` adds the
shares.  Shared experts are added by every share alike.

The held experts run over a sort-based dispatch: per batch row the
(token, slot) pairs are ranked within their expert's queue and the
first C of each held expert are gathered into a (B, count, C, D) buffer;
pairs past C are dropped.  ``capacity_factor`` None makes C the chunk's
token count, which no queue can pass (a token picks an expert at most
once), so nothing is dropped.

Memory knob: the sequence is processed in ``seq_chunks`` sequential
chunks (lax.scan), bounding the dispatch buffers.  Decode (S == 1)
merges the batch into a single dispatch group so expert capacity stays
~B*k/E instead of forcing one slot per (row, expert).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.config import ModelConfig


def _pin(x, batch_axes, *rest):
    """with_sharding_constraint helper (no-op outside a mesh context)."""
    if batch_axes is None:
        return x
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(x, P(batch_axes, *rest))


def moe_init(key, cfg: ModelConfig):
    mc = cfg.moe
    ks = jax.random.split(key, 5)
    _, e = mc.held_range
    d, f = cfg.d_model, mc.d_ff

    def stack(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5

    p = {
        "router": L.dense_init(ks[0], d, mc.num_experts),
        "wi": stack(ks[1], (e, d, f), d),
        "wg": stack(ks[2], (e, d, f), d),
        "wo": stack(ks[3], (e, f, d), f),
    }
    if mc.num_shared_experts:
        width = mc.shared_d_ff or mc.d_ff * mc.num_shared_experts
        p["shared"] = L.swiglu_init(ks[4], d, width)
    return p


def route(logits, mc):
    """Router logits (..., E) -> (gates (..., k) f32, ids (..., k),
    probs (..., E) f32)."""
    probs = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    scores = probs
    if mc.topk_method == "group_limited_greedy":
        g = mc.n_group
        grouped = probs.reshape(*probs.shape[:-1], g, mc.num_experts // g)
        _, top = jax.lax.top_k(grouped.max(axis=-1), mc.topk_group)
        keep = jnp.any(top[..., :, None] == jnp.arange(g), axis=-2)
        scores = jnp.where(keep[..., None], grouped, 0.0).reshape(
            probs.shape)
    gates, ids = jax.lax.top_k(scores, mc.experts_per_token)
    if mc.norm_topk_prob:
        gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)
    else:
        gates = gates * mc.routed_scaling_factor
    return gates, ids, probs


def _capacity(tokens: int, mc) -> int:
    if mc.capacity_factor is None:
        return tokens
    c = int(tokens * mc.experts_per_token * mc.capacity_factor
            / mc.num_experts)
    return max(4, -(-c // 4) * 4)  # >=4, multiple of 4


def _dispatch_indices(ids, num_experts: int, capacity: int):
    """ids: (B, T, k). Returns (src (B,E,C) token index or T=invalid,
    dest slot per (B,T,k), keep mask)."""
    b, t, k = ids.shape
    flat = ids.reshape(b, t * k)
    order = jnp.argsort(flat, axis=-1, stable=True)          # (B, Tk)
    sorted_ids = jnp.take_along_axis(flat, order, axis=-1)
    counts = jnp.sum(sorted_ids[:, :, None] ==
                     jnp.arange(num_experts)[None, None, :], axis=1)
    seg_start = jnp.cumsum(counts, axis=-1) - counts          # (B, E)
    rank_sorted = (jnp.arange(t * k)[None, :]
                   - jnp.take_along_axis(seg_start, sorted_ids, axis=-1))
    # scatter ranks back to unsorted (token, k) order
    rank = jnp.zeros((b, t * k), rank_sorted.dtype).at[
        jnp.arange(b)[:, None], order].set(rank_sorted)
    keep = rank < capacity
    dest = jnp.where(keep, rank, capacity)                    # (B, Tk)
    # src[b, e, c] = flat token index filling slot (e, c); sentinel = t
    lin = flat * (capacity + 1) + dest                        # (B, Tk)
    src = jnp.full((b, num_experts * (capacity + 1)), t * k, jnp.int32)
    src = src.at[jnp.arange(b)[:, None], lin].set(
        jnp.arange(t * k, dtype=jnp.int32)[None, :], mode="drop")
    src = src.reshape(b, num_experts, capacity + 1)[:, :, :capacity]
    src_tok = jnp.minimum(src // k, t)                        # token index
    return src_tok, dest, keep


def _expert_ffn(p, xin):
    """xin: (B, E, C, D) -> (B, E, C, D), per-expert SwiGLU."""
    h = jax.nn.silu(jnp.einsum("becd,edf->becf", xin, p["wg"].astype(xin.dtype)))
    h = h * jnp.einsum("becd,edf->becf", xin, p["wi"].astype(xin.dtype))
    return jnp.einsum("becf,efd->becd", h, p["wo"].astype(xin.dtype))


def _dispatch(p, mc, x, gates, ids, first, count, batch_axes):
    """Held experts over their first C pairs each (capacity dispatch);
    the weighted sum over a token's slots is float32."""
    b, t, d = x.shape
    cap = _capacity(t, mc)
    src_tok, dest, keep = _dispatch_indices(ids, mc.num_experts, cap)
    if count != mc.num_experts:
        src_tok = jax.lax.dynamic_slice_in_dim(src_tok, first, count, 1)
    xpad = jnp.concatenate([x, jnp.zeros((b, 1, d), x.dtype)], axis=1)
    xin = xpad[jnp.arange(b)[:, None, None], src_tok]         # (B,e,C,D)
    # Keep the dispatch gather LOCAL to the batch shard (E replicated);
    # the expert einsum then slices its E shard for free.  Without the
    # pin GSPMD partial-gathers across batch shards and all-reduces the
    # full (B,E,C,D) buffer (measured: 2.7 GB x layers, §Perf granite).
    if mc.dispatch_pin:
        xin = _pin(xin, batch_axes, None, None, None)
    yout = _expert_ffn(p, xin)                                # (B,e,C,D)
    if mc.dispatch_pin:
        yout = _pin(yout, batch_axes, None, None, None)
    # Combine: gather each (token, k) slot's result and weight by its gate.
    # (A scatter-add combine over the E-sharded buffer was hypothesized to
    # let GSPMD emit partial sums + one small all-reduce; MEASURED WORSE —
    # GSPMD all-gathers both scatter operands, 2.5x the collective bytes.
    # Hypothesis refuted; see EXPERIMENTS.md §Perf granite iteration 3.)
    ybuf = yout.reshape(b, count * cap, d)
    flat = ids.reshape(b, -1) - first
    held = (flat >= 0) & (flat < count)
    lin = jnp.clip(flat * cap + jnp.minimum(dest, cap - 1), 0,
                   count * cap - 1)
    gathered = jnp.take_along_axis(
        ybuf, lin[:, :, None].astype(jnp.int32), axis=1)      # (B,Tk,D)
    w = (gates.reshape(b, -1) * (keep & held).astype(gates.dtype))
    return (gathered.astype(jnp.float32) * w[:, :, None]).reshape(
        b, t, mc.experts_per_token, d).sum(axis=2)


def _chunk(p, cfg: ModelConfig, x, first, count, batch_axes=None):
    """x: (B, T, D) one sequence chunk -> (held part, aux loss, held
    (token, slot) pairs, gates (B, T, k), ids (B, T, k))."""
    mc = cfg.moe
    b, t, _ = x.shape
    gates, ids, probs = route(L.dense(p["router"], x), mc)
    out = _dispatch(p, mc, x, gates, ids, first, count, batch_axes)
    pairs = jnp.sum((ids >= first) & (ids < first + count), dtype=jnp.int32)
    # router load-balancing auxiliary loss (Switch-style), returned for logs
    me = probs.mean(axis=(0, 1))
    ce = jnp.zeros_like(me).at[ids.reshape(-1)].add(
        jnp.ones((b * t * mc.experts_per_token,), jnp.float32)
    ) / (b * t * mc.experts_per_token)
    aux = mc.num_experts * jnp.sum(me * ce)
    return out, aux, pairs, gates, ids


def _held_part(p, cfg: ModelConfig, x, first, count, batch_axes=None):
    """x: (B, S, D) -> (the held experts' part (B,S,D), aux, pairs,
    gates (B,S,k), ids (B,S,k))."""
    mc = cfg.moe
    b, s, d = x.shape
    if s == 1:
        out, aux, pairs, gates, ids = _chunk(p, cfg, x.reshape(1, b, d),
                                             first, count)
        k = mc.experts_per_token
        return (out.reshape(b, 1, d), aux, pairs, gates.reshape(b, 1, k),
                ids.reshape(b, 1, k))
    if mc.seq_chunks > 1 and s % mc.seq_chunks == 0:
        t = s // mc.seq_chunks
        xs = x.reshape(b, mc.seq_chunks, t, d).transpose(1, 0, 2, 3)

        def body(_, xc):
            return None, _chunk(p, cfg, xc, first, count, batch_axes)

        _, (outs, auxs, pairs, gates, ids) = jax.lax.scan(body, None, xs)

        def unchunk(a):
            return a.transpose(1, 0, 2, 3).reshape(b, s, a.shape[-1])
        return (unchunk(outs), auxs.mean(), pairs.sum(), unchunk(gates),
                unchunk(ids))
    return _chunk(p, cfg, x, first, count, batch_axes)


def _stats(aux, pairs, gates, ids):
    return {"aux": aux, "held_pairs": pairs, "gates": gates, "ids": ids}


def moe_apply(p, cfg: ModelConfig, x, batch_axes=None):
    """x: (B, S, D) -> (out (B,S,D) float32, stats): the held experts'
    part plus the shared experts; stats holds the router's ``aux`` loss, the
    ``held_pairs`` and each token's ``gates`` and expert ``ids``
    (B, S, k)."""
    first, count = cfg.moe.held_range
    with jax.named_scope("moe:experts"):
        out, *stats = _held_part(p, cfg, x, first, count, batch_axes)
        if "shared" in p:
            out = out + L.swiglu(p["shared"], x)
    return out, _stats(*stats)


# ----------------------------------------------------- shard_map dispatch --

def moe_apply_shard_map(p, cfg: ModelConfig, x, batch_axes=None, mesh=None):
    """Manual expert-parallel dispatch via shard_map (beyond-GSPMD path).

    Observation (EXPERIMENTS.md §Perf): activations are replicated across
    the "model" axis, so every model rank computes ITS OWN experts' part
    (the held share ``first = rank * count``) with ZERO communication
    and the shares meet in ONE psum of the (B, T, D) output over
    "model" — instead of GSPMD's all-reduce/all-gather of full dispatch
    buffers.

    Falls back to moe_apply when no mesh/model axis is available, at
    decode (S == 1), or when num_experts % model_size != 0.
    """
    from functools import partial as _partial

    from jax.experimental.shard_map import shard_map
    from jax.sharding import PartitionSpec as P

    mc = cfg.moe
    b, s, d = x.shape
    if (mesh is None or batch_axes is None
            or "model" not in getattr(mesh, "axis_names", ())
            or s == 1 or mc.num_experts % mesh.shape["model"] != 0):
        return moe_apply(p, cfg, x, batch_axes)

    count = mc.num_experts // mesh.shape["model"]
    bspec = P(batch_axes, None, None)
    espec = P("model", None, None)
    rspec = P(None, None)

    @_partial(shard_map, mesh=mesh,
              in_specs=(bspec, rspec, espec, espec, espec),
              out_specs=bspec)
    def run(xl, router, wg, wi, wo):
        first = jax.lax.axis_index("model") * count
        local = {"router": {"w": router}, "wg": wg, "wi": wi, "wo": wo}
        part = _held_part(local, cfg, xl, first, count)[0]
        return jax.lax.psum(part, "model")          # THE one collective

    with jax.named_scope("moe:experts"):
        out = run(x, p["router"]["w"], p["wg"], p["wi"], p["wo"])
        # router aux loss (cheap global recompute, for logging parity)
        gates, ids, probs = route(L.dense(p["router"], x), mc)
        me = probs.mean(axis=(0, 1))
        ce = jnp.zeros_like(me).at[ids.reshape(-1)].add(
            jnp.ones((b * s * mc.experts_per_token,), jnp.float32)
        ) / (b * s * mc.experts_per_token)
        aux = mc.num_experts * jnp.sum(me * ce)
        if "shared" in p:
            out = out + L.swiglu(p["shared"], x)
    return out, _stats(aux, jnp.int32(ids.size), gates, ids)

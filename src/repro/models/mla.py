"""DeepSeek-V2 Multi-head Latent Attention (MLA).

The KV cache stores the COMPRESSED latent c_kv (kv_lora_rank) plus the
shared RoPE key (rope_head_dim) — the memory win that defines MLA.

Two decode paths (cfg.mla.decode_mode):
  "decompress" — expand the whole latent cache to per-head K/V each step
                 (naive baseline; FLOPs ~ S * kvlr * H * (dn + dv)).
  "absorbed"   — fold W^UK into the query and W^UV into the output and
                 attend directly in latent space (FLOPs ~ S * H * kvlr).
The absorbed path is the §Perf-optimized variant; both are tested equal.

RoPE may be YaRN-scaled (``cfg.mla.yarn``, DeepSeek-V2's published
setting): YaRN's frequencies, and the softmax scale multiplied by
``mscale(factor, mscale_all_dim)**2``.  The published code de-interleaves
the rope dimensions before rotating them; here the halves are rotated as
they lie, which for seeded weights is a fixed relabelling of the rope
columns of ``wq_b`` and ``wkv_a``.

Decode takes one position per batch row (a scalar broadcasts): row b
writes its latent at slot ``pos[b]`` and attends to slots ``<= pos[b]``,
so slots past a row's length (padding, an earlier answer) are never read.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.models import layers as L
from repro.models.config import BlockSpec, ModelConfig


def mla_init(key, cfg: ModelConfig, spec: BlockSpec):
    m = cfg.mla
    h = cfg.num_heads
    dq = m.nope_head_dim + m.rope_head_dim
    ks = jax.random.split(key, 6)
    return {
        "wq_a": L.dense_init(ks[0], cfg.d_model, m.q_lora_rank),
        "q_ln": L.norm_init(m.q_lora_rank),
        "wq_b": L.dense_init(ks[1], m.q_lora_rank, h * dq),
        "wkv_a": L.dense_init(ks[2], cfg.d_model,
                              m.kv_lora_rank + m.rope_head_dim),
        "kv_ln": L.norm_init(m.kv_lora_rank),
        "wkv_b": L.dense_init(ks[3], m.kv_lora_rank,
                              h * (m.nope_head_dim + m.v_head_dim)),
        "wo": L.dense_init(ks[4], h * m.v_head_dim, cfg.d_model),
    }


def _queries(p, cfg, x, positions, spec):
    m = cfg.mla
    b, s, _ = x.shape
    h = cfg.num_heads
    cq = L.rms_norm(p["q_ln"], L.dense(p["wq_a"], x), cfg.norm_eps)
    q = L.dense(p["wq_b"], cq).reshape(
        b, s, h, m.nope_head_dim + m.rope_head_dim)
    q_nope, q_rope = jnp.split(q, [m.nope_head_dim], axis=-1)
    cos, sin = L.rope_tables(positions, m.rope_head_dim, spec.rope_base,
                             m.yarn)
    q_rope = L.apply_rope(q_rope, cos, sin)
    return q_nope, q_rope


def _latents(p, cfg, x, positions, spec):
    m = cfg.mla
    ckv_kr = L.dense(p["wkv_a"], x)
    c_kv, k_rope = jnp.split(ckv_kr, [m.kv_lora_rank], axis=-1)
    c_kv = L.rms_norm(p["kv_ln"], c_kv, cfg.norm_eps)
    cos, sin = L.rope_tables(positions, m.rope_head_dim, spec.rope_base,
                             m.yarn)
    k_rope = L.apply_rope(k_rope[:, :, None, :], cos, sin)[:, :, 0, :]
    return c_kv, k_rope


def _yarn_factor(m) -> float:
    """YaRN's factor on the softmax scale (1 without YaRN)."""
    if m.yarn is None or not m.yarn.mscale_all_dim:
        return 1.0
    return L.yarn_mscale(m.yarn.factor, m.yarn.mscale_all_dim) ** 2


def _expand_kv(p, cfg, c_kv):
    """latent (B,S,r) -> per-head k_nope,v (B,S,H,*)."""
    m = cfg.mla
    b, s, _ = c_kv.shape
    kv = L.dense(p["wkv_b"], c_kv).reshape(
        b, s, cfg.num_heads, m.nope_head_dim + m.v_head_dim)
    return jnp.split(kv, [m.nope_head_dim], axis=-1)


def _full_attention(p, cfg, spec, q_nope, q_rope, c_kv, k_rope, positions,
                    kvpos):
    m = cfg.mla
    b, s = q_nope.shape[:2]
    k_nope, v = _expand_kv(p, cfg, c_kv)
    k = jnp.concatenate(
        [k_nope, jnp.broadcast_to(k_rope[:, :, None, :],
                                  (*k_nope.shape[:3], m.rope_head_dim))],
        axis=-1)
    q = jnp.concatenate([q_nope, q_rope], axis=-1)
    factor = _yarn_factor(m)
    if factor != 1.0:
        q = (q.astype(jnp.float32) * factor).astype(q.dtype)
    out = L.attention_any(q, k, v, positions, kvpos, causal=True,
                          window=spec.window, kv_chunk=cfg.attn_kv_chunk)
    return L.dense(p["wo"], out.reshape(b, s, cfg.num_heads * m.v_head_dim))


def mla_apply(p, cfg: ModelConfig, spec: BlockSpec, x, positions):
    q_nope, q_rope = _queries(p, cfg, x, positions, spec)
    c_kv, k_rope = _latents(p, cfg, x, positions, spec)
    return _full_attention(p, cfg, spec, q_nope, q_rope, c_kv, k_rope,
                           positions, positions)


def mla_cache_init(cfg: ModelConfig, batch: int, ctx_len: int,
                   dtype=jnp.bfloat16):
    m = cfg.mla
    return {
        "ckv": jnp.zeros((batch, ctx_len, m.kv_lora_rank), dtype),
        "krope": jnp.zeros((batch, ctx_len, m.rope_head_dim), dtype),
    }


def mla_prefill(p, cfg, spec, x, positions, cache):
    q_nope, q_rope = _queries(p, cfg, x, positions, spec)
    c_kv, k_rope = _latents(p, cfg, x, positions, spec)
    out = _full_attention(p, cfg, spec, q_nope, q_rope, c_kv, k_rope,
                          positions, positions)
    s = x.shape[1]
    cache = {
        "ckv": cache["ckv"].at[:, :s].set(c_kv.astype(cache["ckv"].dtype)),
        "krope": cache["krope"].at[:, :s].set(
            k_rope.astype(cache["krope"].dtype)),
    }
    return out, cache


def mla_decode(p, cfg: ModelConfig, spec: BlockSpec, x, pos, cache,
               layer=None):
    """One token per row at positions ``pos`` (shape () or (B,)).  With
    ``layer``, ``cache`` is a stack of layers' caches and this layer's
    is at that index: the new row is written into the stack in place."""
    with jax.named_scope("mla:decode"):
        return _decode(p, cfg, spec, x, pos, cache, layer)


def _decode(p, cfg, spec, x, pos, cache, layer):
    m = cfg.mla
    b = x.shape[0]
    h = cfg.num_heads
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32), (b,))
    positions = pos[:, None]                  # (B, 1)
    q_nope, q_rope = _queries(p, cfg, x, positions, spec)
    c_kv_t, k_rope_t = _latents(p, cfg, x, positions, spec)
    at = (jnp.arange(b), pos) if layer is None else (layer, jnp.arange(b),
                                                       pos)
    cache = {
        "ckv": cache["ckv"].at[at].set(
            c_kv_t[:, 0].astype(cache["ckv"].dtype)),
        "krope": cache["krope"].at[at].set(
            k_rope_t[:, 0].astype(cache["krope"].dtype)),
    }
    ckv, krope = ((cache["ckv"], cache["krope"]) if layer is None else
                  (cache["ckv"][layer], cache["krope"][layer]))
    ckv = ckv.astype(x.dtype)                 # (B,S,r)
    krope = krope.astype(x.dtype)             # (B,S,dr)
    kvpos = jnp.arange(ckv.shape[1], dtype=jnp.int32)
    if m.decode_mode == "decompress":
        out = _full_attention(p, cfg, spec, q_nope, q_rope, ckv, krope,
                              positions, kvpos)
        return out, cache

    # --- absorbed path: attend in latent space -----------------------------
    wkv_b = p["wkv_b"]["w"].astype(x.dtype).reshape(
        m.kv_lora_rank, h, m.nope_head_dim + m.v_head_dim)
    w_uk = wkv_b[..., : m.nope_head_dim]      # (r, H, dn)
    w_uv = wkv_b[..., m.nope_head_dim:]       # (r, H, dv)
    # q_lat[b,1,h,r] = q_nope . W^UK
    q_lat = jnp.einsum("bqhn,rhn->bqhr", q_nope, w_uk)
    scale = (m.nope_head_dim + m.rope_head_dim) ** -0.5 * _yarn_factor(m)
    s_lat = jnp.einsum("bqhr,bkr->bhqk", q_lat, ckv)
    s_rope = jnp.einsum("bqhd,bkd->bhqk", q_rope, krope)
    scores = (s_lat + s_rope).astype(jnp.float32) * scale
    bias = L._mask_bias(positions, kvpos, causal=True, window=spec.window)
    probs = jax.nn.softmax(scores + bias[:, None], axis=-1)
    ctx_lat = jnp.einsum("bhqk,bkr->bqhr", probs.astype(x.dtype), ckv)
    out = jnp.einsum("bqhr,rhv->bqhv", ctx_lat, w_uv)
    out = L.dense(p["wo"], out.reshape(b, 1, h * m.v_head_dim))
    return out, cache

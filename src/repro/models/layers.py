"""Shared neural-net layers: norms, RoPE, attention paths, MLPs.

Conventions
-----------
- Parameters are plain nested dicts of fp32 arrays; compute is bf16 with
  fp32 softmax/norm internals.
- Attention uses materialized-GQA (KV heads repeated to Q heads at use
  time) so head sharding never straddles a reshape — robust under GSPMD.
- Three attention paths:
    * plain     — scores materialized; small Sq*Skv or decode.
    * chunked   — online-softmax scan over KV chunks (memory-bounded path
                  for 32k+ prefill / encoder forward).
    * local     — sliding-window attention via the two-block trick:
                  O(S * 2W) FLOPs, used by windowed layers at train/prefill.
- Masks are computed from ABSOLUTE positions (qpos/kvpos arrays), which
  makes ring-buffer decode caches and padding uniform everywhere.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

Array = jax.Array

NEG_INF = -1e30


# ------------------------------------------------------------- init utils

def dense_init(key, d_in: int, d_out: int, *, bias: bool = False, scale=None):
    w = jax.random.normal(key, (d_in, d_out), jnp.float32)
    w = w * (scale if scale is not None else d_in ** -0.5)
    p = {"w": w}
    if bias:
        p["b"] = jnp.zeros((d_out,), jnp.float32)
    return p


def dense(p, x):
    y = x @ p["w"].astype(x.dtype)
    if "b" in p:
        y = y + p["b"].astype(x.dtype)
    return y


def norm_init(dim: int):
    return {"scale": jnp.ones((dim,), jnp.float32)}


def rms_norm(p, x, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * p["scale"]).astype(x.dtype)


# ------------------------------------------------------------------- RoPE

def yarn_mscale(scale: float, mscale: float = 1.0) -> float:
    return 1.0 if scale <= 1 else 0.1 * mscale * math.log(scale) + 1.0


def _yarn_inv_freq(dim: int, base: float, yarn) -> np.ndarray:
    """DeepseekV2YarnRotaryEmbedding's frequencies: the interpolated
    ``freq / factor`` and the original ``freq`` blended by a linear ramp
    between the correction dimensions of ``beta_fast`` and ``beta_slow``
    rotations over the original context."""
    def corr_dim(rotations):
        return (dim * math.log(yarn.original_max_position
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(corr_dim(yarn.beta_fast)), 0)
    high = min(math.ceil(corr_dim(yarn.beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32) / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    keep = 1.0 - ramp
    return (extra / yarn.factor) * (1 - keep) + extra * keep


def rope_tables(positions: Array, dim: int, base: float, yarn=None):
    """cos/sin tables for `positions` (any leading shape) -> (..., dim/2).
    With ``yarn`` (a :class:`~repro.models.config.YarnConfig`) the
    frequencies and the table scale are YaRN's."""
    if yarn is None:
        inv_freq = 1.0 / (base ** (np.arange(0, dim, 2, dtype=np.float32)
                                   / dim))
        scale = 1.0
    else:
        inv_freq = _yarn_inv_freq(dim, base, yarn)
        scale = (yarn_mscale(yarn.factor, yarn.mscale)
                 / yarn_mscale(yarn.factor, yarn.mscale_all_dim))
    ang = positions.astype(jnp.float32)[..., None] * inv_freq
    return jnp.cos(ang) * scale, jnp.sin(ang) * scale


def apply_rope(x: Array, cos: Array, sin: Array):
    """x: (B, S, H, D); cos/sin: (B?, S, D/2) or (S, D/2)."""
    while cos.ndim < x.ndim - 1:
        cos, sin = cos[None], sin[None]
    cos = cos[..., None, :]  # broadcast over heads -> (..., S, 1, D/2)
    sin = sin[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# -------------------------------------------------------------- attention

def _repeat_kv(k: Array, num_q_heads: int):
    reps = num_q_heads // k.shape[2]
    return jnp.repeat(k, reps, axis=2) if reps > 1 else k


def _mask_bias(qpos, kvpos, *, causal: bool, window: int):
    """(..., Sq, Skv) additive bias from absolute positions.

    kvpos < 0 marks invalid (unwritten) cache slots.
    """
    q = qpos[..., :, None].astype(jnp.int32)
    k = kvpos[..., None, :].astype(jnp.int32)
    ok = k >= 0
    if causal:
        ok &= k <= q
    if window > 0:
        ok &= k > q - window
    return jnp.where(ok, 0.0, NEG_INF).astype(jnp.float32)


def plain_attention(q, k, v, qpos, kvpos, *, causal=True, window=0):
    """q: (B,Sq,H,D); k,v: (B,Skv,Hkv,D); qpos: (B,Sq) or (Sq,);
    kvpos: (B,Skv) or (Skv,)."""
    h = q.shape[2]
    k, v = _repeat_kv(k, h), _repeat_kv(v, h)
    scale = q.shape[-1] ** -0.5
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) * scale
    bias = _mask_bias(qpos, kvpos, causal=causal, window=window)
    if bias.ndim == 2:
        bias = bias[None, None]
    else:
        bias = bias[:, None]
    s = s + bias
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v.dtype), v)


def _flash_fwd(q, k, v, qpos, kvpos, causal, window, chunk):
    """Online-softmax forward. Returns (out (b,h,sq,dv), lse (b,h,sq)).

    Supports dv != d_qk (e.g. MLA: 192-dim QK, 128-dim V)."""
    b, sq, h, d = q.shape
    dv = v.shape[-1]
    skv = k.shape[1]
    n_chunks = skv // chunk
    kc = k.reshape(b, n_chunks, chunk, h, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, h, dv).transpose(1, 0, 2, 3, 4)
    kvp = kvpos.reshape(b, n_chunks, chunk).transpose(1, 0, 2)
    scale = d ** -0.5

    def body(carry, xs):
        m, l, acc = carry
        kci, vci, kvpi = xs
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kci).astype(jnp.float32) * scale
        s = s + _mask_bias(qpos, kvpi, causal=causal, window=window)[:, None]
        m_new = jnp.maximum(m, s.max(axis=-1))
        p = jnp.exp(s - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bhqk,bkhd->bhqd", p.astype(vci.dtype), vci).astype(jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, h, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, h, sq), jnp.float32)
    a0 = jnp.zeros((b, h, sq, dv), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(body, (m0, l0, a0), (kc, vc, kvp))
    l_safe = jnp.maximum(l, 1e-30)
    out = acc / l_safe[..., None]
    lse = m + jnp.log(l_safe)
    return out, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7))
def _flash_attention(q, k, v, qpos, kvpos, causal, window, chunk):
    out, _ = _flash_fwd(q, k, v, qpos, kvpos, causal, window, chunk)
    return out.transpose(0, 2, 1, 3).astype(q.dtype)


def _flash_vjp_fwd(q, k, v, qpos, kvpos, causal, window, chunk):
    out, lse = _flash_fwd(q, k, v, qpos, kvpos, causal, window, chunk)
    outq = out.transpose(0, 2, 1, 3).astype(q.dtype)
    return outq, (q, k, v, qpos, kvpos, outq, lse)


def _flash_vjp_bwd(causal, window, chunk, res, g):
    """Flash backward: recompute p per KV chunk from saved lse; saves no
    per-chunk accumulators (the standard memory-optimal scheme)."""
    q, k, v, qpos, kvpos, out, lse = res
    b, sq, h, d = q.shape
    dv = v.shape[-1]
    skv = k.shape[1]
    n_chunks = skv // chunk
    scale = d ** -0.5
    g = g.astype(jnp.float32)                        # (b, sq, h, dv)
    outf = out.astype(jnp.float32)
    # delta = rowsum(dO * O)  (b, h, sq)
    delta = jnp.einsum("bqhd,bqhd->bhq", g, outf)
    kc = k.reshape(b, n_chunks, chunk, h, d).transpose(1, 0, 2, 3, 4)
    vc = v.reshape(b, n_chunks, chunk, h, dv).transpose(1, 0, 2, 3, 4)
    kvp = kvpos.reshape(b, n_chunks, chunk).transpose(1, 0, 2)

    def body(dq_acc, xs):
        kci, vci, kvpi = xs
        s = jnp.einsum("bqhd,bkhd->bhqk", q, kci).astype(jnp.float32) * scale
        s = s + _mask_bias(qpos, kvpi, causal=causal, window=window)[:, None]
        p = jnp.exp(s - lse[..., None])              # (b,h,sq,k)
        dv = jnp.einsum("bhqk,bqhd->bkhd", p, g)
        dp = jnp.einsum("bqhd,bkhd->bhqk", g, vci.astype(jnp.float32))
        ds = p * (dp - delta[..., None]) * scale
        dq_acc = dq_acc + jnp.einsum("bhqk,bkhd->bqhd", ds,
                                     kci.astype(jnp.float32))
        dk = jnp.einsum("bhqk,bqhd->bkhd", ds, q.astype(jnp.float32))
        return dq_acc, (dk, dv)

    dq0 = jnp.zeros((b, sq, h, d), jnp.float32)
    dq, (dk, dv) = jax.lax.scan(body, dq0, (kc, vc, kvp))
    dk = dk.transpose(1, 0, 2, 3, 4).reshape(b, skv, h, d)
    dv = dv.transpose(1, 0, 2, 3, 4).reshape(b, skv, h, dv.shape[-1])
    return (dq.astype(q.dtype), dk.astype(k.dtype), dv.astype(v.dtype),
            None, None)


_flash_attention.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


def chunked_attention(q, k, v, qpos, kvpos, *, causal=True, window=0,
                      chunk=1024):
    """Flash attention (online softmax, custom memory-optimal VJP)."""
    b, sq, h, d = q.shape
    skv = k.shape[1]
    if skv % chunk:
        pad = chunk - skv % chunk
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kvp = kvpos if kvpos.ndim == 2 else kvpos[None]
        kvpos = jnp.pad(kvp, ((0, 0), (0, pad)), constant_values=-1)
        skv += pad
    k, v = _repeat_kv(k, h), _repeat_kv(v, h)
    if kvpos.ndim == 1:
        kvpos = kvpos[None]
    if qpos.ndim == 1:
        qpos = qpos[None]
    kvpos = jnp.broadcast_to(kvpos, (b, skv))
    qpos = jnp.broadcast_to(qpos, (b, sq))
    return _flash_attention(q, k, v, qpos, kvpos, causal, window, chunk)


def local_attention(q, k, v, *, window: int, q_offset=0):
    """Causal sliding-window attention for full sequences (train/prefill).

    Two-block trick: pad S to multiples of W=window; queries in block i
    attend keys in blocks {i-1, i} with position masking, giving
    O(S * 2W) instead of O(S^2).
    """
    b, s, h, d = q.shape
    w = window
    k, v = _repeat_kv(k, h), _repeat_kv(v, h)
    pad = (-s) % w
    if pad:
        qp = jnp.pad(q, ((0, 0), (0, pad), (0, 0), (0, 0)))
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        qp, kp, vp = q, k, v
    sp = s + pad
    n = sp // w
    qb = qp.reshape(b, n, w, h, d)
    kb = kp.reshape(b, n, w, h, d)
    vb = vp.reshape(b, n, w, h, d)
    # previous block (block -1 is zeros with invalid positions)
    k_prev = jnp.concatenate([jnp.zeros_like(kb[:, :1]), kb[:, :-1]], axis=1)
    v_prev = jnp.concatenate([jnp.zeros_like(vb[:, :1]), vb[:, :-1]], axis=1)
    k2 = jnp.concatenate([k_prev, kb], axis=2)  # (b, n, 2w, h, d)
    v2 = jnp.concatenate([v_prev, vb], axis=2)
    scale = d ** -0.5
    qpos = (jnp.arange(n)[:, None] * w + jnp.arange(w)[None, :])  # (n, w)
    kvpos = (jnp.arange(n)[:, None] - 1) * w + jnp.arange(2 * w)[None, :]
    valid_kv = (kvpos >= 0) & (kvpos < s)
    kvpos = jnp.where(valid_kv, kvpos, -1)
    bias = _mask_bias(qpos, kvpos, causal=True, window=w)  # (n, w, 2w)

    def one_block(args):
        qb_i, k2_i, v2_i, bias_i = args  # (b, w, h, d), (b, 2w, h, d), ...
        sco = jnp.einsum("bqhd,bkhd->bhqk", qb_i, k2_i)
        sco = sco.astype(jnp.float32) * scale + bias_i[None, None]
        p = jax.nn.softmax(sco, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(v2_i.dtype), v2_i)

    # sequential over blocks: bounds live fp32 scores to one block's worth
    out = jax.lax.map(one_block,
                      (qb.transpose(1, 0, 2, 3, 4),
                       k2.transpose(1, 0, 2, 3, 4),
                       v2.transpose(1, 0, 2, 3, 4), bias))
    out = out.transpose(1, 0, 2, 3, 4).reshape(b, sp, h, d)
    return out[:, :s]


def attention_any(q, k, v, qpos, kvpos, *, causal=True, window=0,
                  kv_chunk=1024, plain_limit=1024 * 1024):
    """Route to the right attention path.

    - decode (sq == 1) and small problems: plain (scores materialized);
    - windowed full-sequence: blocked local attention, O(S * 2W);
    - everything else: online-softmax chunked attention (memory-bounded).
    """
    sq, skv = q.shape[1], k.shape[1]
    if window > 0 and causal and sq == skv and sq > window:
        return local_attention(q, k, v, window=window)
    if sq * skv <= plain_limit or sq == 1:
        return plain_attention(q, k, v, qpos, kvpos, causal=causal,
                               window=window)
    return chunked_attention(q, k, v, qpos, kvpos, causal=causal,
                             window=window, chunk=kv_chunk)


# ------------------------------------------------------------------- MLPs

def swiglu_init(key, d_model: int, d_ff: int):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "wi": dense_init(k1, d_model, d_ff),
        "wg": dense_init(k2, d_model, d_ff),
        "wo": dense_init(k3, d_ff, d_model),
    }


def swiglu(p, x):
    h = jax.nn.silu(dense(p["wg"], x)) * dense(p["wi"], x)
    return dense(p["wo"], h)


def gelu_mlp_init(key, d_model: int, d_ff: int):
    k1, k2 = jax.random.split(key)
    return {"wi": dense_init(k1, d_model, d_ff, bias=True),
            "wo": dense_init(k2, d_ff, d_model, bias=True)}


def gelu_mlp(p, x):
    return dense(p["wo"], jax.nn.gelu(dense(p["wi"], x)))

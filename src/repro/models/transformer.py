"""Model assembly: embeddings/frontends, residual blocks, scan-over-layers.

Layout of a parameter tree (all plain dicts; leaves fp32):

  {"embed": {...}, "prefix": [block...], "pattern": [stacked block...],
   "suffix": [block...], "final_norm": {...}, "lm_head": {...}}

`pattern` holds one entry per pattern POSITION; each entry is a block tree
whose leaves carry a leading `repeats` axis, consumed by `lax.scan`.

The paper's technique enters through `cfg.approx`: when enabled, both
residual-stream adds of every block run through the configured approximate
adder in fixed point (cfg.approx.residual_add -> repro.ax engine, STE
gradients).  The residual stream is then carried in float32, which holds
every value of the fixed-point format exactly (bf16 would round away the
low bits the adder works on); norms and matmuls still take bf16 inputs.

Decode carries the pattern's stacked cache through the layer scan and
hands each block its layer index.  A mixer in ``IN_PLACE_DECODE`` writes
its new row into the (donated) stacked buffer in place and reads its
layer where it lies; the others slice their layer out and set it back.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.models import attention as ATT
from repro.models import layers as L
from repro.models import mla as MLAm
from repro.models import moe as MOEm
from repro.models import rglru as RGm
from repro.models import ssd as SSDm
from repro.models.config import (
    ATTN, CROSS, GELU, MLA, MOE, NONE, RGLRU, SSD, SWIGLU,
    BlockSpec, ModelConfig,
)

Params = Dict[str, Any]

#: Dtype of matmul and norm inputs.
COMPUTE_DTYPE = jnp.bfloat16

#: Mixers whose decode takes the pattern's stacked cache and the layer
#: index.  Slicing a layer out and setting it back copies the layer: at
#: the DeepSeek-V2 cell's size (1.2 GB of latent cache a layer) the
#: decode step's temporaries grow from 0.3 GB to 1.9 GB
#: (tests/test_tpu_compile.py).  The other mixers' decode states are
#: small, or their decode is not on a measured path.
IN_PLACE_DECODE = frozenset({MLA})


# ------------------------------------------------------------------ init --

def block_init(key, cfg: ModelConfig, spec: BlockSpec) -> Params:
    kmix, kmlp, _ = jax.random.split(key, 3)
    p: Params = {"ln1": L.norm_init(cfg.d_model)}
    if spec.mixer == ATTN:
        p["mixer"] = ATT.attn_init(kmix, cfg, spec)
    elif spec.mixer == CROSS:
        p["mixer"] = ATT.cross_attn_init(kmix, cfg, spec)
    elif spec.mixer == MLA:
        p["mixer"] = MLAm.mla_init(kmix, cfg, spec)
    elif spec.mixer == RGLRU:
        p["mixer"] = RGm.rglru_init(kmix, cfg, spec)
    elif spec.mixer == SSD:
        p["mixer"] = SSDm.ssd_init(kmix, cfg, spec)
    if spec.mlp != NONE:
        p["ln2"] = L.norm_init(cfg.d_model)
        if spec.mlp == SWIGLU:
            p["mlp"] = L.swiglu_init(kmlp, cfg.d_model, cfg.d_ff)
        elif spec.mlp == GELU:
            p["mlp"] = L.gelu_mlp_init(kmlp, cfg.d_model, cfg.d_ff)
        elif spec.mlp == MOE:
            p["mlp"] = MOEm.moe_init(kmlp, cfg)
    if spec.mixer == CROSS:
        p["gate_mlp"] = jnp.zeros((), jnp.float32)
    return p


def _cast(tree, dtype):
    return jax.tree.map(lambda a: a.astype(dtype), tree)


def init_params(rng, cfg: ModelConfig, dtype=None) -> Params:
    """Seeded parameters, float32.  With ``dtype`` the same values are
    cast to it, each block (and each layer of the pattern) built and
    cast under a jit of its own, so that at most one layer's float32
    leaves exist at a time."""
    cfg.validate()
    if dtype is None:
        def make(fn, *args):
            return fn(*args)
    else:
        jitted = {}

        def make(fn, *args):
            if fn not in jitted:
                jitted[fn] = jax.jit(lambda *a: _cast(fn(*a), dtype))
            return jitted[fn](*args)

    keys = jax.random.split(rng, 8)
    p: Params = {}
    d = cfg.d_model
    if cfg.audio is not None:
        p["frontend"] = make(functools.partial(
            L.dense_init, d_in=cfg.audio.feat_dim, d_out=d, bias=True),
            keys[0])
    else:
        p["embed"] = make(lambda k: {"table": jax.random.normal(
            k, (cfg.padded_vocab, d), jnp.float32) * d ** -0.5}, keys[0])
    if cfg.vision is not None:
        p["vis_adapter"] = make(functools.partial(
            L.dense_init, d_in=cfg.vision.embed_dim, d_out=d), keys[1])
    blocks = {s: functools.partial(block_init, cfg=cfg, spec=s)
              for s in cfg.all_blocks()}
    p["prefix"] = [make(blocks[s], k) for k, s in
                   zip(jax.random.split(keys[2], max(1, len(cfg.prefix))),
                       cfg.prefix)]
    p["suffix"] = [make(blocks[s], k) for k, s in
                   zip(jax.random.split(keys[3], max(1, len(cfg.suffix))),
                       cfg.suffix)]
    pattern = []
    for i, s in enumerate(cfg.pattern):
        ks = jax.random.split(jax.random.fold_in(keys[4], i), cfg.repeats)
        if dtype is None:
            pattern.append(jax.vmap(blocks[s])(ks))
        else:
            layers = [make(blocks[s], k) for k in ks]
            pattern.append(jax.tree.map(lambda *a: jnp.stack(a), *layers))
    p["pattern"] = pattern
    p["final_norm"] = make(functools.partial(L.norm_init, d))
    p["lm_head"] = make(functools.partial(
        L.dense_init, d_in=d, d_out=cfg.padded_vocab), keys[5])
    return p


# --------------------------------------------------------------- caches --

def block_cache_init(cfg: ModelConfig, spec: BlockSpec, batch: int,
                     ctx_len: int, dtype=jnp.bfloat16) -> Params:
    if spec.mixer == ATTN:
        return ATT.attn_cache_init(cfg, spec, batch, ctx_len, dtype)
    if spec.mixer == CROSS:
        sv = cfg.vision.seq_len
        shape = (batch, sv, cfg.num_kv_heads, cfg.head_dim)
        return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}
    if spec.mixer == MLA:
        return MLAm.mla_cache_init(cfg, batch, ctx_len, dtype)
    if spec.mixer == RGLRU:
        return RGm.rglru_cache_init(cfg, batch, dtype)
    if spec.mixer == SSD:
        return SSDm.ssd_cache_init(cfg, batch, dtype)
    raise ValueError(spec.mixer)


def init_cache(cfg: ModelConfig, batch: int, ctx_len: int,
               dtype=jnp.bfloat16) -> Params:
    c: Params = {
        "prefix": [block_cache_init(cfg, s, batch, ctx_len, dtype)
                   for s in cfg.prefix],
        "suffix": [block_cache_init(cfg, s, batch, ctx_len, dtype)
                   for s in cfg.suffix],
    }
    pattern = []
    for s in cfg.pattern:
        one = block_cache_init(cfg, s, batch, ctx_len, dtype)
        pattern.append(jax.tree.map(
            lambda x: jnp.broadcast_to(x[None], (cfg.repeats, *x.shape)), one))
    c["pattern"] = pattern
    return c


# ---------------------------------------------------------------- blocks --

def _zero_stats():
    return {"aux": jnp.zeros((), jnp.float32),
            "held_pairs": jnp.zeros((), jnp.int32)}


def _operand(v, taps: bool):
    """A residual add's operand.  Tapped, it is one materialized value
    for the add and the tap: XLA on TPU may otherwise fuse its producer
    (a bf16 matmul) into the add's quantization at excess precision, so
    that the add reads other low bits than the tap shows."""
    return jax.lax.optimization_barrier(v) if taps else v


def _taps(cfg: ModelConfig, x, mix, mid, out, gates, ids):
    """One block's intermediates (see :func:`block_apply`)."""
    b, s, _ = x.shape
    k = cfg.moe.experts_per_token if cfg.moe is not None else 0
    return {"x": x, "mix": mix, "mid": mid, "out": out,
            "gates": (jnp.zeros((b, s, k), jnp.float32)
                      if gates is None else gates),
            "ids": jnp.zeros((b, s, k), jnp.int32) if ids is None else ids}


def block_apply(p: Params, cfg: ModelConfig, spec: BlockSpec, x, ctx,
                cache: Optional[Params], mode: str, batch_axes=None,
                mesh=None, layer=None, taps: bool = False):
    """mode: 'full' | 'prefill' | 'decode'. Returns (x, new_cache, stats):
    stats holds the router's ``aux`` loss and the ``held_pairs``, the
    (token, slot) pairs that reached this chip's experts.

    ``layer`` (decode only): ``cache`` is the pattern's stacked cache and
    this block's entry is at that index of its leading axis.

    ``taps``: stats also holds ``taps``, the block's intermediates: its
    input ``x``, the mixer's output ``mix`` and the first residual add's
    result ``mid``, the MLP's output ``out`` (the operands of the two
    adds, in the residual stream's dtype) and, for an MoE MLP, each
    token's ``gates`` and expert ``ids`` (zeros otherwise).  The block's
    result is the next block's ``x``."""
    x_in = x
    h = L.rms_norm(p["ln1"], x, cfg.norm_eps).astype(COMPUTE_DTYPE)
    stack = cache
    sliced = layer is not None and spec.mixer not in IN_PLACE_DECODE
    if sliced:
        cache = jax.tree.map(lambda a: a[layer], stack)
    new_cache = cache
    if spec.mixer == ATTN:
        if mode == "full":
            mix = ATT.attn_apply(p["mixer"], cfg, spec, h, ctx["positions"])
        elif mode == "prefill":
            mix, new_cache = ATT.attn_prefill(
                p["mixer"], cfg, spec, h, ctx["positions"], cache)
        else:
            mix, new_cache = ATT.attn_decode(
                p["mixer"], cfg, spec, h, ctx["pos"], cache)
    elif spec.mixer == CROSS:
        if mode in ("full", "prefill"):
            kv = ATT.cross_kv(p["mixer"], cfg, ctx["vis"])
            if mode == "prefill":
                new_cache = {"k": kv[0].astype(cache["k"].dtype),
                             "v": kv[1].astype(cache["v"].dtype)}
        else:
            kv = (cache["k"].astype(h.dtype), cache["v"].astype(h.dtype))
        mix = ATT.cross_attn_apply(p["mixer"], cfg, spec, h, kv)
    elif spec.mixer == MLA:
        if mode == "full":
            mix = MLAm.mla_apply(p["mixer"], cfg, spec, h, ctx["positions"])
        elif mode == "prefill":
            mix, new_cache = MLAm.mla_prefill(
                p["mixer"], cfg, spec, h, ctx["positions"], cache)
        else:
            mix, new_cache = MLAm.mla_decode(
                p["mixer"], cfg, spec, h, ctx["pos"], cache,
                None if sliced else layer)
    elif spec.mixer == RGLRU:
        if mode == "full":
            mix, _ = RGm.rglru_apply(p["mixer"], cfg, spec, h)
        elif mode == "prefill":
            mix, new_cache = RGm.rglru_prefill(p["mixer"], cfg, spec, h, cache)
        else:
            mix, new_cache = RGm.rglru_decode(p["mixer"], cfg, spec, h, cache)
    elif spec.mixer == SSD:
        if mode == "full":
            mix, _ = SSDm.ssd_apply(p["mixer"], cfg, spec, h)
        elif mode == "prefill":
            mix, new_cache = SSDm.ssd_prefill(p["mixer"], cfg, spec, h, cache)
        else:
            mix, new_cache = SSDm.ssd_decode(p["mixer"], cfg, spec, h, cache)
    else:
        raise ValueError(spec.mixer)

    if sliced:
        new_cache = jax.tree.map(lambda a, n: a.at[layer].set(n), stack,
                                 new_cache)
    mix = _operand(mix.astype(x.dtype), taps)
    x = cfg.approx.residual_add(x, mix)
    stats = _zero_stats()
    mid, out, route = x, jnp.zeros_like(x), {"gates": None, "ids": None}
    if spec.mlp != NONE:
        h2 = L.rms_norm(p["ln2"], x, cfg.norm_eps).astype(COMPUTE_DTYPE)
        if spec.mlp == MOE:
            if cfg.moe.use_shard_map and mode != "decode":
                out, st = MOEm.moe_apply_shard_map(
                    p["mlp"], cfg, h2, batch_axes=batch_axes, mesh=mesh)
            else:
                out, st = MOEm.moe_apply(p["mlp"], cfg, h2,
                                         batch_axes=batch_axes)
            stats = {"aux": st["aux"], "held_pairs": st["held_pairs"]}
            route = {"gates": st["gates"], "ids": st["ids"]}
        elif spec.mlp == SWIGLU:
            out = L.swiglu(p["mlp"], h2)
        else:
            out = L.gelu_mlp(p["mlp"], h2)
        if spec.mixer == CROSS:
            out = jnp.tanh(p["gate_mlp"]).astype(out.dtype) * out
        out = _operand(out.astype(x.dtype), taps)
        x = cfg.approx.residual_add(x, out)
    if taps:
        stats["taps"] = _taps(cfg, x_in, mix, mid, out, **route)
    return x, new_cache, stats


# --------------------------------------------------------------- forward --

def _shard_act(x, batch_axes, seq_shard=False):
    if batch_axes is None:
        return x
    from jax.sharding import PartitionSpec as P
    rest = [None] * (x.ndim - 1)
    if seq_shard and x.ndim >= 3:
        rest[0] = "model"  # sequence dim over TP (Megatron-SP region)
    spec = P(batch_axes, *rest)
    return jax.lax.with_sharding_constraint(x, spec)


def embed_input(params, cfg: ModelConfig, batch, compute_dtype=jnp.bfloat16,
                need_vision=True):
    """batch: {"tokens": (B,S) i32} or {"frames": (B,S,feat)} (+"vision")."""
    if cfg.audio is not None:
        x = L.dense(params["frontend"], batch["frames"].astype(compute_dtype))
    else:
        x = params["embed"]["table"].astype(compute_dtype)[batch["tokens"]]
    ctx = {}
    if cfg.vision is not None and need_vision:
        ctx["vis"] = L.dense(params["vis_adapter"],
                             batch["vision"].astype(compute_dtype))
    return x, ctx


def forward(params, cfg: ModelConfig, batch, *, mode: str = "full",
            cache: Optional[Params] = None, pos=None, batch_axes=None,
            mesh=None, return_prelogits: bool = False, taps: bool = False):
    """Returns (logits, new_cache, stats), stats as :func:`block_apply`'s
    summed over the blocks.  Decode takes ``pos`` of shape () or (B,),
    one position per row; MLA blocks honour per-row positions.

    ``taps``: stats also holds ``taps``, the blocks' intermediates (see
    :func:`block_apply`) in the layout of the parameters (``prefix`` and
    ``suffix`` lists, ``pattern`` stacked over the repeats), and
    ``final``, the last block's result."""
    x, ctx = embed_input(params, cfg, batch, need_vision=(mode != "decode"))
    if cfg.approx.enabled:
        x = x.astype(jnp.float32)
    b, s = x.shape[:2]
    if mode == "decode":
        ctx["pos"] = pos
    else:
        ctx["positions"] = jnp.arange(s, dtype=jnp.int32)
    # SP applies to full-sequence passes (training AND prefill); decode
    # steps have seq length 1.
    ss = cfg.seq_shard and mode in ("full", "prefill")
    x = _shard_act(x, batch_axes, ss)

    stats = _zero_stats()
    tapped = {"prefix": [], "pattern": [], "suffix": []}
    empty = {"prefix": [None] * len(cfg.prefix),
             "suffix": [None] * len(cfg.suffix),
             "pattern": [None] * len(cfg.pattern)}
    cache_in = cache if cache is not None else empty
    cache_out = {"prefix": [], "suffix": [], "pattern": []}

    def add(stats, st):
        """Sums ``st`` into ``stats``; returns (stats, st's taps)."""
        st = dict(st)
        tap = st.pop("taps", None)
        return jax.tree.map(jnp.add, stats, st), tap

    def apply_one(p, spec, x, c):
        if cfg.remat == "block" and mode == "full":
            fn = jax.checkpoint(
                functools.partial(block_apply, cfg=cfg, spec=spec, mode=mode,
                                  batch_axes=batch_axes, mesh=mesh))
            return fn(p, x=x, ctx=ctx, cache=c)
        return block_apply(p, cfg, spec, x, ctx, c, mode,
                           batch_axes=batch_axes, mesh=mesh, taps=taps)

    for p, spec, c in zip(params["prefix"], cfg.prefix, cache_in["prefix"]):
        x, nc, st = apply_one(p, spec, x, c)
        x = _shard_act(x, batch_axes, ss)
        cache_out["prefix"].append(nc)
        stats, tap = add(stats, st)
        tapped["prefix"].append(tap)

    if cfg.repeats > 0 and cfg.pattern:
        # Decode carries the stacked cache (see the module docstring);
        # prefill scans over the cache's layers and stacks the new ones.
        decode = mode == "decode"

        def body(carry, xs):
            x, stats, stacks, layer = carry
            pslices, cslices = xs
            stacks, ys, taps_ = list(stacks), [], []
            for i, spec in enumerate(cfg.pattern):
                c = stacks[i] if decode else (
                    None if cslices is None else cslices[i])
                x, nc, st = block_apply(
                    pslices[i], cfg, spec, x, ctx, c, mode,
                    batch_axes=batch_axes, mesh=mesh,
                    layer=layer if decode else None, taps=taps)
                x = _shard_act(x, batch_axes, ss)
                stats, tap = add(stats, st)
                taps_.append(tap)
                if decode:
                    stacks[i] = nc
                else:
                    ys.append(nc)
            out = (tuple(ys) if cache is not None and not decode else 0,
                   tuple(taps_))
            return (x, stats, tuple(stacks), layer + 1), out

        if cfg.remat == "block" and mode == "full":
            body = jax.checkpoint(body)
        stacks = tuple(cache_in["pattern"]) if decode else ()
        cslices = (tuple(cache_in["pattern"])
                   if cache is not None and not decode else None)
        (x, stats, stacks, _), (ys, taps_) = jax.lax.scan(
            body, (x, stats, stacks, jnp.int32(0)),
            (tuple(params["pattern"]), cslices))
        if decode:
            cache_out["pattern"] = list(stacks)
        elif cache is not None:
            cache_out["pattern"] = list(ys)
        tapped["pattern"] = list(taps_)

    for p, spec, c in zip(params["suffix"], cfg.suffix, cache_in["suffix"]):
        x, nc, st = apply_one(p, spec, x, c)
        x = _shard_act(x, batch_axes, ss)
        cache_out["suffix"].append(nc)
        stats, tap = add(stats, st)
        tapped["suffix"].append(tap)
    if taps:
        stats["taps"] = dict(tapped, final=x)

    x = L.rms_norm(params["final_norm"], x, cfg.norm_eps).astype(
        COMPUTE_DTYPE)
    if mode in ("prefill", "decode") and cfg.causal:
        x = x[:, -1:]  # only the last position's logits are needed
    if return_prelogits:
        return x, (cache_out if cache is not None else None), stats
    logits = L.dense(params["lm_head"], x)
    return logits, (cache_out if cache is not None else None), stats


# ------------------------------------------------------------------ loss --

def softmax_cross_entropy(logits, labels):
    """Shard-friendly CE: the gold logit is extracted with an iota compare
    + masked sum (partitionable along a model-sharded vocab axis), never
    with take_along_axis (which would all-gather the full logits)."""
    logits = logits.astype(jnp.float32)
    logz = jax.nn.logsumexp(logits, axis=-1)
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                          logits.ndim - 1)
    gold = jnp.sum(jnp.where(vocab_iota == labels[..., None], logits, 0.0),
                   axis=-1)
    return logz - gold


def loss_fn(params, cfg: ModelConfig, batch, batch_axes=None, mesh=None):
    x, _, stats = forward(params, cfg, batch, mode="full",
                          batch_axes=batch_axes, mesh=mesh,
                          return_prelogits=True)
    aux = stats["aux"]

    # Head + CE under remat: the (B, S, V) logits (and the fp32 softmax
    # internals) are recomputed during backward instead of being saved.
    @jax.checkpoint
    def head_loss(w, x, labels):
        logits = L.dense(w, x)
        if cfg.padded_vocab != cfg.vocab_size:
            # mask padded vocab slots to -inf (exact CE over the true vocab)
            viota = jax.lax.broadcasted_iota(jnp.int32, logits.shape,
                                             logits.ndim - 1)
            logits = jnp.where(viota < cfg.vocab_size, logits,
                               jnp.asarray(L.NEG_INF, logits.dtype))
        return softmax_cross_entropy(logits, labels).mean()

    ce = head_loss(params["lm_head"], x, batch["labels"])
    loss = ce + 0.01 * aux
    return loss, {"ce": ce, "aux": aux}

"""The ``deepseek_v2`` system: DeepSeek-V2 through the program's own
entry points, ``launch.steps.make_prefill_step`` and
``make_decode_step`` (``models.transformer.forward`` over MLA and the
held experts), with the configured adder in every residual add.

The configuration file carries the published ``config.json`` keys, cut
to one chip's share (its ``deployment`` names the whole): the router
keeps all ``deployment.n_routed_experts`` outputs, and the layer
computes the ``n_routed_experts`` experts at ``deployment.held``.
"""

from __future__ import annotations

import functools


def model_config(cfg: dict, backend: str):
    """The program's ``ModelConfig`` for a configuration file."""
    from repro.models.config import (MLA, MOE, SWIGLU, BlockSpec,
                                     MLAConfig, ModelConfig, MoEConfig,
                                     YarnConfig)
    from repro.numerics.approx_ops import make_numerics
    dep = cfg["deployment"]
    rope = cfg["rope_scaling"]
    base = float(cfg["rope_theta"])
    dense = cfg["first_k_dense_replace"]
    first, count = dep["held"]
    if count != cfg["n_routed_experts"]:
        raise ValueError(f"{cfg['name']}: held {dep['held']} is not "
                         f"{cfg['n_routed_experts']} experts")
    add = cfg["adder"]
    return ModelConfig(
        name=cfg["name"], family="moe",
        d_model=cfg["hidden_size"],
        num_heads=cfg["num_attention_heads"],
        num_kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["qk_nope_head_dim"],
        d_ff=cfg["intermediate_size"],
        vocab_size=cfg["vocab_size"],
        prefix=(BlockSpec(mixer=MLA, mlp=SWIGLU, rope_base=base),) * dense,
        pattern=(BlockSpec(mixer=MLA, mlp=MOE, rope_base=base),),
        repeats=cfg["num_hidden_layers"] - dense,
        norm_eps=cfg["rms_norm_eps"],
        tie_embeddings=cfg["tie_word_embeddings"],
        mla=MLAConfig(
            kv_lora_rank=cfg["kv_lora_rank"], q_lora_rank=cfg["q_lora_rank"],
            rope_head_dim=cfg["qk_rope_head_dim"],
            nope_head_dim=cfg["qk_nope_head_dim"],
            v_head_dim=cfg["v_head_dim"], decode_mode=cfg["decode_mode"],
            yarn=YarnConfig(
                factor=rope["factor"],
                original_max_position=rope["original_max_position_embeddings"],
                beta_fast=rope["beta_fast"], beta_slow=rope["beta_slow"],
                mscale=rope["mscale"], mscale_all_dim=rope["mscale_all_dim"])),
        moe=MoEConfig(
            num_experts=dep["n_routed_experts"],
            experts_per_token=cfg["num_experts_per_tok"],
            d_ff=cfg["moe_intermediate_size"],
            num_shared_experts=cfg["n_shared_experts"],
            shared_d_ff=cfg["n_shared_experts"] * cfg["moe_intermediate_size"],
            capacity_factor=None, seq_chunks=cfg["moe_seq_chunks"],
            topk_method=cfg["topk_method"], n_group=cfg["n_group"],
            topk_group=cfg["topk_group"],
            routed_scaling_factor=float(cfg["routed_scaling_factor"]),
            norm_topk_prob=cfg["norm_topk_prob"], held=(first, count)),
        approx=make_numerics(add["kind"], "residual", n_bits=add["n_bits"],
                             frac_bits=add["frac_bits"],
                             lsm_bits=add["lsm_bits"],
                             const_bits=add["const_bits"], backend=backend),
        attn_kv_chunk=cfg["attn_kv_chunk"],
        remat="none",
    ).validate()


class DeepSeekV2:
    """The model's steps and state, as a user of the program makes them.
    Its decode step returns the blocks' intermediates (``taps``) beside
    the logits, for the comparison with the reference."""

    def __init__(self, cfg: dict, backend: str):
        import jax.numpy as jnp
        from repro.launch import steps
        self.config = cfg
        self.model = model_config(cfg, backend)
        self.engine = self.model.approx.engine
        self.dtype = jnp.dtype(cfg["dtype"])
        self.cache_dtype = jnp.dtype(cfg["cache_dtype"])
        self.decode = steps.make_decode_step(self.model, taps=True)

    @property
    def vocab(self) -> int:
        return self.model.vocab_size

    @property
    def moe_layers(self) -> int:
        return self.model.repeats * len(self.model.pattern)

    @property
    def held_experts(self) -> int:
        return self.model.moe.held_range[1]

    def init_params(self, weights):
        """The program's parameter tree from ``weights``, a mapping of the
        published names (``model.layers.3.self_attn.q_a_proj.weight``,
        ...) to arrays in the published ``(out, in)`` layout, read one
        at a time; leaves in the configured dtype."""
        import jax
        import jax.numpy as jnp
        cfg, dt = self.config, self.dtype
        first, count = cfg["deployment"]["held"]

        def vec(name):
            return jnp.asarray(weights[name], dt)

        def dense(name):
            return {"w": jnp.asarray(weights[name], dt).T}

        def swiglu(pre):
            return {"wg": dense(pre + "gate_proj.weight"),
                    "wi": dense(pre + "up_proj.weight"),
                    "wo": dense(pre + "down_proj.weight")}

        def block(i):
            pre = f"model.layers.{i}."
            att = pre + "self_attn."
            p = {"ln1": {"scale": vec(pre + "input_layernorm.weight")},
                 "mixer": {
                     "wq_a": dense(att + "q_a_proj.weight"),
                     "q_ln": {"scale": vec(att + "q_a_layernorm.weight")},
                     "wq_b": dense(att + "q_b_proj.weight"),
                     "wkv_a": dense(att + "kv_a_proj_with_mqa.weight"),
                     "kv_ln": {"scale": vec(att + "kv_a_layernorm.weight")},
                     "wkv_b": dense(att + "kv_b_proj.weight"),
                     "wo": dense(att + "o_proj.weight")},
                 "ln2": {"scale": vec(pre + "post_attention_layernorm.weight")}}
            if i < cfg["first_k_dense_replace"]:
                p["mlp"] = swiglu(pre + "mlp.")
                return p
            experts = [swiglu(f"{pre}mlp.experts.{e}.")
                       for e in range(first, first + count)]
            p["mlp"] = {"router": dense(pre + "mlp.gate.weight"),
                        "shared": swiglu(pre + "mlp.shared_experts.")}
            for k in ("wg", "wi", "wo"):
                p["mlp"][k] = jnp.stack([x[k]["w"] for x in experts])
            return p

        def stack(layers):
            return jax.tree.map(lambda *a: jnp.stack(a), *layers)

        dense_n = cfg["first_k_dense_replace"]
        table = vec("model.embed_tokens.weight")
        pad = self.model.padded_vocab - table.shape[0]
        head = dense("lm_head.weight")
        if pad:
            table = jnp.pad(table, ((0, pad), (0, 0)))
            head = {"w": jnp.pad(head["w"], ((0, 0), (0, pad)))}
        return {
            "embed": {"table": table},
            "prefix": [block(i) for i in range(dense_n)],
            "suffix": [],
            "pattern": [stack([block(i) for i in range(
                dense_n, cfg["num_hidden_layers"])])],
            "final_norm": {"scale": vec("model.norm.weight")},
            "lm_head": head}

    @staticmethod
    def layer_taps(stats):
        """The decode step's intermediates, one dict per layer in order,
        each array with the batch axis first and the position axis gone:
        ``x``, ``mix``, ``mid``, ``out`` (B, D), ``gates``, ``ids``
        (B, k), and ``next``, the layer's result."""
        taps = stats["taps"]
        layers = list(taps["prefix"])
        for stacked in taps["pattern"]:
            n = stacked["x"].shape[0]
            layers += [{k: v[i] for k, v in stacked.items()}
                       for i in range(n)]
        layers += list(taps["suffix"])
        out = [{k: v[:, 0] for k, v in t.items()} for t in layers]
        for t, nxt in zip(out, out[1:]):
            t["next"] = nxt["x"]
        out[-1]["next"] = taps["final"][:, 0]
        return out

    @functools.lru_cache(maxsize=None)
    def prefill(self, ctx_len: int):
        """``(params, {"tokens": (B, S)}) -> (logits, cache, stats)``."""
        from repro.launch import steps
        return steps.make_prefill_step(self.model, ctx_len)

    def init_cache(self, batch: int, ctx_len: int):
        from repro.models import transformer as T
        return T.init_cache(self.model, batch, ctx_len, self.cache_dtype)


def build(cfg: dict, backend: str):
    return DeepSeekV2(cfg, backend)

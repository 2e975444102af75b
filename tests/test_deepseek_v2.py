"""DeepSeek-V2 through the program's normal path against its plain
reference (``chipbench/configs/deepseek-v2-ep20-haloc16.py``), at the
smoke widths of ``deepseek_v2_236b.smoke_config`` with seeded weights:
prefill and per-row decode through the latent cache, YaRN tables,
group-limited routing, expert layers that drop nothing, the expert-parallel
share, the bf16 parameter build, and the steps' spans."""

from __future__ import annotations

import dataclasses
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench.cells import load_module
from chipbench.system import deepseek_v2 as system_lib
from repro.configs.deepseek_v2_236b import smoke_config
from repro.launch import steps
from repro.models import layers as L
from repro.models import moe as MOEm
from repro.models import transformer as T
from repro.models.config import YarnConfig

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(REPO, "chipbench", "configs",
                      "deepseek-v2-ep20-haloc16")


def _smoke_json():
    """The cell's configuration file at smoke_config's widths."""
    with open(CONFIG + ".json") as f:
        cfg = json.load(f)
    s = smoke_config()
    m, e = s.mla, s.moe
    cfg.update({
        "hidden_size": s.d_model, "intermediate_size": s.d_ff,
        "num_attention_heads": s.num_heads, "num_key_value_heads": s.num_heads,
        "kv_lora_rank": m.kv_lora_rank, "q_lora_rank": m.q_lora_rank,
        "qk_nope_head_dim": m.nope_head_dim,
        "qk_rope_head_dim": m.rope_head_dim, "v_head_dim": m.v_head_dim,
        "moe_intermediate_size": e.d_ff, "num_experts_per_tok":
        e.experts_per_token, "n_group": e.n_group, "topk_group": e.topk_group,
        "n_routed_experts": e.num_experts, "vocab_size": s.vocab_size,
        "num_hidden_layers": 1 + s.repeats, "moe_seq_chunks": 2,
        "attn_kv_chunk": 8})
    cfg["deployment"] = dict(cfg["deployment"],
                             n_routed_experts=e.num_experts,
                             held=[0, e.num_experts])
    return cfg


@pytest.fixture(scope="module")
def reference():
    """The plain reference.  With the system below it states the same
    semantics as the program: the exact 16-bit add on Q8.8 values in
    every residual add (the approximate adder would turn rounding
    differences into differences of whole low bits; the cell's check
    bounds those)."""
    return load_module(CONFIG + ".py", "dsv2_reference")


def _weights(cfg, seed):
    """The cell's seeded weights by published name."""
    loop = load_module(os.path.join(REPO, "chipbench", "loop",
                                    "mla_decode.py"), "dsv2_loop")
    return loop.Weights(cfg, seed)


@pytest.fixture(scope="module")
def exact_system():
    cfg = _smoke_json()
    cfg["adder"] = dict(cfg["adder"], kind="accurate")
    return cfg, system_lib.build(cfg, "jax")


def test_prefill_then_per_row_decode_with_a_rewind_matches_reference(
        exact_system, reference):
    """Rows of different lengths decode at their own positions through
    one cache; row 2 rewinds to its document's end and starts a new
    answer over the old one.  Every step's logits agree with the
    reference's full forward pass over the row's document and answer."""
    cfg, sysm = exact_system
    ctx, lengths = 24, np.array([7, 12, 10])
    rng = np.random.default_rng(5)
    vocab = cfg["vocab_size"]
    docs = rng.integers(0, vocab, (3, ctx), dtype=np.int32)
    docs[np.arange(ctx)[None] >= lengths[:, None]] = 0
    weights = _weights(cfg, 11)
    params = sysm.init_params(weights)
    _, cache, _ = sysm.prefill(ctx)(params, {"tokens": jnp.asarray(docs)})
    seqs = [list(docs[b, :lengths[b]]) for b in range(3)]
    pos = lengths.copy()
    errs = []
    for step in range(5):
        if step == 3:                      # row 2 starts a new answer
            pos[2] = lengths[2]
            seqs[2] = seqs[2][:lengths[2]]
        tok = rng.integers(0, vocab, 3, dtype=np.int32)
        logits, cache, _ = sysm.decode(params, jnp.asarray(tok[:, None]),
                                       jnp.asarray(pos, jnp.int32), cache)
        for b in range(3):
            seqs[b].append(int(tok[b]))
            tokens = np.zeros(ctx, np.int32)
            tokens[:len(seqs[b])] = seqs[b]
            want = np.asarray(reference.reference(
                weights, tokens, len(seqs[b]), cfg)["logits"])
            got = np.asarray(logits[b, 0], np.float32)
            errs.append(np.max(np.abs(got - want)) / np.std(want))
        pos += 1
    # bf16 matmuls against float32 ones: a few parts in a hundred of the
    # logits' spread at these widths.  A wrong position, mask or slot
    # reads another token's latent and is off by the whole spread.
    assert max(errs) < 0.1, errs


def _published_yarn(positions, dim=64, base=10000.0, factor=40.0,
                    orig=4096, beta_fast=32, beta_slow=1, mscale=0.707,
                    mscale_all_dim=0.707):
    """DeepseekV2YarnRotaryEmbedding._set_cos_sin_cache, in numpy (the
    half-width table: the published one repeats it twice)."""
    def corr_dim(rot):
        return (dim * math.log(orig / (rot * 2 * math.pi))) / (
            2 * math.log(base))

    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float32) / dim)
    freq_inter = 1.0 / (factor * base ** (np.arange(0, dim, 2,
                                                    dtype=np.float32) / dim))
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    inv_freq = freq_inter * (1 - mask) + freq_extra * mask
    freqs = np.outer(np.asarray(positions, np.float32), inv_freq)

    def get_mscale(scale, m):
        return 1.0 if scale <= 1 else 0.1 * m * math.log(scale) + 1.0

    m = get_mscale(factor, mscale) / get_mscale(factor, mscale_all_dim)
    return np.cos(freqs) * m, np.sin(freqs) * m


def test_yarn_tables_match_the_published_formula():
    positions = np.array([0, 4095, 16383])
    cos, sin = L.rope_tables(jnp.asarray(positions), 64, 10000.0,
                             YarnConfig())
    want_cos, want_sin = _published_yarn(positions)
    np.testing.assert_allclose(np.asarray(cos), want_cos, atol=2e-3)
    np.testing.assert_allclose(np.asarray(sin), want_sin, atol=2e-3)
    # and YaRN is not plain RoPE: the low frequencies are interpolated
    plain, _ = L.rope_tables(jnp.asarray(positions), 64, 10000.0)
    assert np.max(np.abs(np.asarray(plain) - want_cos)) > 0.5


def test_yarn_softmax_scale_is_mscale_squared():
    from repro.models import mla
    assert mla._yarn_factor(smoke_config().mla) == pytest.approx(
        (0.1 * 0.707 * math.log(40) + 1) ** 2)
    assert mla._yarn_factor(smoke_config().mla) == pytest.approx(1.5896,
                                                                abs=1e-4)


def test_group_limited_routing_against_a_hand_computation():
    """8 experts in 4 groups of 2, the 2 best groups kept, top-3 inside,
    gates times 16.  Logits are chosen so each softmax probability is
    known: p = e^l / sum e^l."""
    mc = dataclasses.replace(smoke_config().moe, n_group=4, topk_group=2,
                             experts_per_token=3)
    probs = np.array([[0.05, 0.20, 0.15, 0.02, 0.18, 0.10, 0.25, 0.05],
                      [0.30, 0.01, 0.01, 0.28, 0.14, 0.12, 0.13, 0.01]])
    gates, ids, _ = MOEm.route(jnp.log(jnp.asarray(probs)), mc)
    # row 0: group bests (0.20, 0.15, 0.18, 0.25) -> groups 3 and 0 ->
    #   experts {6: .25, 1: .20, 7: .05, 0: .05}: top-3 is 6, 1 and one
    #   of the 0.05s (expert 4's 0.18 is in a dropped group).
    # row 1: group bests (0.30, 0.28, 0.14, 0.13) -> groups 0 and 1 ->
    #   0: .30, 3: .28, then 1 or 2 at .01 (4's 0.14 is dropped).
    ids = np.asarray(ids)
    gates = np.asarray(gates)
    assert list(ids[0, :2]) == [6, 1] and ids[0, 2] in (0, 7)
    assert list(ids[1, :2]) == [0, 3] and ids[1, 2] in (1, 2)
    np.testing.assert_allclose(gates[0], 16 * np.array([0.25, 0.20, 0.05]),
                               rtol=1e-5)
    np.testing.assert_allclose(gates[1], 16 * np.array([0.30, 0.28, 0.01]),
                               rtol=1e-5)
    # greedy over the same scores takes expert 4 in both rows
    greedy = dataclasses.replace(mc, topk_method="greedy")
    _, gids, _ = MOEm.route(jnp.log(jnp.asarray(probs)), greedy)
    assert 4 in np.asarray(gids)[0] and 4 in np.asarray(gids)[1]


def _moe_cfg(**kw):
    cfg = smoke_config()
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=None, seq_chunks=1, **kw))


def _dense_moe(p, cfg, x):
    """Per-token reference of the layer: every (token, slot) pair through
    its expert, float32."""
    mc = cfg.moe
    xf = np.asarray(x, np.float32).reshape(-1, cfg.d_model)
    gates, ids, _ = MOEm.route(
        jnp.asarray(xf) @ jnp.asarray(p["router"]["w"], jnp.float32), mc)
    gates, ids = np.asarray(gates), np.asarray(ids)
    first, count = mc.held_range
    out = np.zeros_like(xf)
    wg, wi, wo = (np.asarray(p[k], np.float32) for k in ("wg", "wi", "wo"))
    for t in range(xf.shape[0]):
        for g, e in zip(gates[t], ids[t]):
            if first <= e < first + count:
                h = xf[t] @ wg[e - first]
                h = h / (1 + np.exp(-h)) * (xf[t] @ wi[e - first])
                out[t] += g * (h @ wo[e - first])
    return out.reshape(x.shape)


def test_no_token_is_dropped_under_routing_skewed_onto_one_expert():
    cfg = _moe_cfg()
    p = MOEm.moe_init(jax.random.key(0), cfg)
    p.pop("shared")
    # the router sends every token to expert 5 first
    w = np.zeros((cfg.d_model, cfg.moe.num_experts), np.float32)
    w[:, 5] = 1.0
    p["router"]["w"] = jnp.asarray(w)
    x = jnp.abs(jax.random.normal(jax.random.key(1), (2, 32, cfg.d_model),
                                  jnp.float32))
    out, st = MOEm.moe_apply(p, cfg, x)
    assert int(st["held_pairs"]) == 2 * 32 * cfg.moe.experts_per_token
    want = _dense_moe(p, cfg, x)
    np.testing.assert_allclose(np.asarray(out, np.float32), want,
                               rtol=2e-2, atol=2e-2 * np.abs(want).max())
    # the dropping layer at the default capacity loses most of expert 5
    drop = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=1.25))
    lossy, _ = MOEm.moe_apply(p, drop, x)
    assert np.abs(np.asarray(lossy) - want).max() > 0.1 * np.abs(want).max()


def test_expert_shares_add_up_to_the_uncut_layer():
    """Four shares of two experts each (as four chips of an expert-
    parallel deployment would hold them): their routed parts, with the
    shared expert that every chip computes alike counted once, add up to
    the uncut layer."""
    cfg = _moe_cfg()
    p = MOEm.moe_init(jax.random.key(2), cfg)
    x = jax.random.normal(jax.random.key(3), (2, 16, cfg.d_model),
                          jnp.float32)
    whole, st = MOEm.moe_apply(p, cfg, x)
    shared = L.swiglu(p["shared"], x)
    total, held = np.asarray(shared), 0
    for first in range(0, 8, 2):
        share = dataclasses.replace(cfg, moe=dataclasses.replace(
            cfg.moe, held=(first, 2)))
        ps = dict(p, **{k: p[k][first:first + 2] for k in ("wg", "wi",
                                                           "wo")})
        part, n = MOEm.moe_apply(ps, share, x)
        total = total + np.asarray(part) - np.asarray(shared)
        held += int(n["held_pairs"])
    assert held == int(st["held_pairs"]) == 2 * 16 * cfg.moe.experts_per_token
    np.testing.assert_allclose(total, np.asarray(whole), rtol=1e-4,
                               atol=1e-4 * np.abs(np.asarray(whole)).max())


def test_published_weights_map_onto_the_program_s_tree(exact_system):
    """Every published weight of the share lands in the program's tree,
    which has the layout, shapes and dtype of the program's own build;
    a held expert's stack entry is that expert's weight, transposed."""
    cfg, sysm = exact_system
    weights = _weights(cfg, 3)
    params = sysm.init_params(weights)
    want = jax.eval_shape(lambda: T.init_params(
        jax.random.key(0), sysm.model, dtype=jnp.bfloat16))
    assert (jax.tree.structure(params) == jax.tree.structure(want))
    for a, b in zip(jax.tree.leaves(params), jax.tree.leaves(want)):
        assert (a.shape, a.dtype) == (b.shape, b.dtype)
    n = sum(int(np.prod(w.shape)) for w in weights.values())
    assert n == sum(a.size for a in jax.tree.leaves(params))
    layer = cfg["first_k_dense_replace"] + 1
    np.testing.assert_array_equal(
        np.asarray(params["pattern"][0]["mlp"]["wo"][1, 2], np.float32),
        np.asarray(weights[f"model.layers.{layer}.mlp.experts.2.down_proj"
                           ".weight"], np.float32).T)


def test_bf16_parameters_are_the_float32_ones_cast():
    cfg = smoke_config()
    key = jax.random.key(4)
    f32 = T.init_params(key, cfg)
    low = T.init_params(key, cfg, dtype=jnp.bfloat16)
    # Under jit XLA may round a draw times its scale differently in the
    # last float32 bit, which can move a bf16 rounding by one step.
    for a, b in zip(jax.tree.leaves(f32), jax.tree.leaves(low)):
        assert b.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(b, np.float32),
                                   np.asarray(a), rtol=2 ** -8, atol=0)


def test_steps_run_inside_their_spans(capture):
    cfg = dataclasses.replace(smoke_config(), remat="none")
    params = T.init_params(jax.random.key(5), cfg)
    toks = jnp.ones((2, 8), jnp.int32)
    with capture() as cap:
        _, cache, _ = steps.make_prefill_step(cfg, 12)(params,
                                                       {"tokens": toks})
        steps.make_decode_step(cfg)(params, toks[:, :1],
                                    jnp.array([8, 9], jnp.int32), cache)
    assert len(cap.named("model:prefill")) == 1
    assert len(cap.named("model:decode")) == 1


def test_residual_stream_is_float32_only_under_the_adder():
    cfg = _smoke_json()
    approx = system_lib.build(cfg, "jax").model
    assert approx.approx.enabled
    plain = dataclasses.replace(approx, approx=smoke_config().approx)
    params = T.init_params(jax.random.key(6), approx, dtype=jnp.bfloat16)
    batch = {"tokens": jnp.ones((1, 4), jnp.int32)}
    for model, dtype in ((approx, jnp.float32), (plain, jnp.bfloat16)):
        x, _, _ = T.forward(params, model, batch, return_prelogits=True)
        jaxpr = jax.make_jaxpr(lambda p: T.forward(
            p, model, batch, return_prelogits=True))(params)
        adds = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "scan"]
        assert x.dtype == jnp.bfloat16      # the head takes bf16
        assert adds                         # the MoE layers are scanned
        carry = adds[0].outvars[0].aval
        assert carry.dtype == dtype


_SHARD_MAP = """
import dataclasses, jax, jax.numpy as jnp, numpy as np
from jax.sharding import Mesh
from repro.configs.deepseek_v2_236b import smoke_config
from repro.models import moe
cfg = smoke_config()
for cap in (None, 8.0):
    c = dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, capacity_factor=cap, seq_chunks=2))
    p = moe.moe_init(jax.random.key(0), c)
    x = jax.random.normal(jax.random.key(1), (4, 16, c.d_model))
    mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
    with mesh:
        a, _ = jax.jit(lambda p, x: moe.moe_apply_shard_map(
            p, c, x, batch_axes=("data",), mesh=mesh))(p, x)
    b, _ = moe.moe_apply(p, c, x)
    print(float(jnp.max(jnp.abs(a - b)) / jnp.max(jnp.abs(b))))
"""


def test_shard_map_ranks_hold_shares_that_add_up_to_the_layer():
    """Under shard_map each rank of the "model" axis computes the held
    share ``first = rank * count`` and one psum adds them: the same
    output as the layer computed whole, dropping or not.  Run on four
    virtual CPU devices in a process of its own."""
    import subprocess
    import sys
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=os.path.join(REPO, "src"))
    out = subprocess.run([sys.executable, "-c", _SHARD_MAP], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-2000:]
    errs = [float(v) for v in out.stdout.split()]
    assert len(errs) == 2 and max(errs) < 1e-5, errs

"""CPU rehearsal of ``dsv2-mla-decode-b64``: DeepSeek-V2's decode cell
in a test-size benchmark copy, its configuration written here at small
widths (``WIDTHS``), with the mix's ``test_size``.  The
cell runs correct and reports its metrics; its control, a cache held in
float8, a router without its group limit, a held expert left out and
expert matmuls on float8 weights each fail the comparison; the work
counts and readers of its per-layer metrics read what they should from
a v5e reading."""

from __future__ import annotations

import json
import os

import pytest

from chipbench import peaks, readings, trace as trace_lib
from chipbench_testutil import REPO, bench_copy, run_cell

CELL = "dsv2-mla-decode-b64"
CONFIG = "deepseek-v2-ep20-haloc16"

#: Smaller widths; the routing shape (160 router outputs of which 8 are
#: held, 8 groups of which 3 are kept, 6 per token, gates times 16), the
#: residual adds, rope, depth and the cut are the configuration's own.
WIDTHS = {"hidden_size": 256, "intermediate_size": 512, "kv_lora_rank": 128,
          "q_lora_rank": 192, "qk_nope_head_dim": 32, "qk_rope_head_dim": 16,
          "v_head_dim": 32, "num_attention_heads": 8,
          "num_key_value_heads": 8, "moe_intermediate_size": 128,
          "vocab_size": 1024, "attn_kv_chunk": 16, "moe_seq_chunks": 2}


def _work():
    from chipbench.cells import load_module
    return load_module(os.path.join(REPO, "chipbench", "work",
                                    "deepseek_v2_decode.py"),
                       "test_work_deepseek_v2_decode")


def _published():
    with open(os.path.join(REPO, "chipbench", "configs",
                           CONFIG + ".json")) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    root = bench_copy(tmp_path_factory.mktemp("dsv2"), workloads={CELL})
    cfg = dict(_published(), **WIDTHS)
    with open(os.path.join(root, "chipbench", "configs", CONFIG + ".json"),
              "w") as f:
        json.dump(cfg, f)
    return root


def test_cell_runs_correct_and_reports_its_end_to_end_metrics(checkout):
    res = run_cell(checkout, CELL)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"gmac_per_s", "setup_s"}
    assert res["metrics"]["gmac_per_s"]["unit"] == "GMAC/s"
    c = res["counters"]
    assert c["steps"] > 0 and res["attempted"] == 8 * c["steps"]
    assert c["tok_per_s"] == pytest.approx(8 * c["steps"] / c["window_s"])
    assert c["step_macs"] == _work().step_macs(8, 64, dict(
        _published(), **WIDTHS))
    assert res["metrics"]["gmac_per_s"]["value"] == pytest.approx(
        c["steps"] * c["step_macs"] / c["window_s"] / 1e9)
    assert c["window_traces"] == 0 and c["window_compiles"] == 0
    assert set(res["checks"]) == {"logit_err", "attn0_err", "add_bad",
                                  "route_off", "moe_err"}
    assert c["compared_logits"] == 4 * 1024
    # 4 sampled steps: 5 layers' two adds over 8 rows of 256, and the 4
    # MoE layers' 8 rows
    assert c["compared_adds"] == 4 * 5 * 2 * 8 * 256
    assert c["compared_moe_rows"] == 4 * 4 * 8
    assert 0 < c["held_pairs"] <= c["steps"] * 8 * 6 * 4


def test_traced_run_reports_the_per_layer_metrics(checkout):
    """On the CPU the shares of a peak or a roofline read nothing (no
    peak table entry) and are left out; the idle share and the expert
    counter are read."""
    res = run_cell(checkout, CELL, trace=1)
    assert res["correct"] is True
    assert set(res["metrics"]) == {"idle_share.dsv2", "expert_tokens.dsv2"}
    c = res["counters"]
    assert res["metrics"]["expert_tokens.dsv2"]["value"] == pytest.approx(
        c["held_pairs"] / (c["steps"] * 8 * 4))


def _fails(res, check):
    assert res["correct"] is False
    assert res["checks"][check]["value"] > res["checks"][check]["limit"]


def test_control_fails_the_comparison(checkout):
    """The exact add in every residual add's place: nearly every element
    of the adds differs from HALOC-AxA's."""
    _fails(run_cell(checkout, CELL, control=1), "add_bad")


def _float8_cache(system):
    import jax.numpy as jnp
    system.cache_dtype = jnp.float8_e4m3fn
    return system


def _no_group_limit(system):
    """The router takes the top 6 of all 160 experts."""
    from chipbench.system import deepseek_v2
    return deepseek_v2.build(dict(system.config, topk_method="greedy"),
                             system.engine.backend.name)


def _with_params(change):
    """A fault in the parameters the system maps from the weights."""
    def patch(system):
        made = system.init_params

        def init_params(weights):
            return change(made(weights))

        system.init_params = init_params
        return system

    return patch


def _drop_expert(p):
    mlp = p["pattern"][0]["mlp"]
    mlp["wo"] = mlp["wo"].at[:, 0].set(0)
    return p


def _float8(p):
    import jax
    import jax.numpy as jnp
    mlp = p["pattern"][0]["mlp"]
    for k in ("wg", "wi", "wo", "shared"):
        mlp[k] = jax.tree.map(lambda a: a.astype(jnp.float8_e4m3fn).astype(
            a.dtype), mlp[k])
    return p


#: Faults of the expert layer: the router without its group limit, a
#: held expert's output left out of every MoE layer, and the expert
#: matmuls (held and shared) on float8 weights.
FAULTS = {"no_group_limit": _no_group_limit,
          "held_expert_left_out": _with_params(_drop_expert),
          "float8_experts": _with_params(_float8)}


def test_cache_held_in_float8_is_not_correct(checkout):
    """The precision below the configuration's bf16 cache: the logits
    barely move (attention averages the rounding over the context),
    layer 0's attention output does."""
    _fails(run_cell(checkout, CELL, patch=_float8_cache), "attn0_err")


@pytest.mark.parametrize("fault,check", [
    ("no_group_limit", "route_off"),
    ("held_expert_left_out", "moe_err"),
    ("float8_experts", "moe_err")])
def test_expert_layer_fault_is_not_correct(checkout, fault, check):
    """Each fault of the expert layer fails the check of that layer,
    which compares it on the step's own input."""
    _fails(run_cell(checkout, CELL, patch=FAULTS[fault]), check)


DEV = "/device:TPU:0"


def _reading(ops, calls, counters=None):
    tr = trace_lib.Trace(devices={DEV: ops},
                         spans=[("cb.window", 0, 1_000_000_000)])
    return readings.Reading(trace=tr, device=DEV, window=tr.window(),
                            device_kind="TPU v5 lite", calls=calls,
                            config=_published(), counters=counters or {})


def test_decode_work_counts_weights_cache_and_absorbed_attention():
    """At the published widths: about 2.0 B parameters on this chip, of
    which the embedding is read for the batch's rows only; 1152 bytes of
    latent per cached token and layer; attention 2*128*(576+512)
    operations per cached token and layer."""
    from chipbench.readings import work_of
    work = work_of("deepseek_v2_decode")
    cfg = _published()
    ops0, bytes0 = work((64, 0), cfg)
    ops1, bytes1 = work((64, 1000), cfg)
    assert bytes1 - bytes0 == 1000 * 5 * 1152
    assert ops1 - ops0 == 1000 * 5 * 2 * 128 * (576 + 512)
    weights = bytes0 / 2
    assert 1.9e9 < weights < 2.0e9     # all but 65.5 M of the embedding
    # per token: 2 operations per weight it multiplies (~1.22 B of them)
    assert 2.3e9 < ops0 / 64 < 2.6e9


def test_step_macs_count_every_slot_of_the_cache():
    """``gmac_per_s``'s count: half the operations of a step whose every
    row attends all 16384 slots, whatever the rows' lengths; about
    12.6 G multiply-accumulates per token at the published widths."""
    w, cfg = _work(), _published()
    macs = w.step_macs(64, 16384, cfg)
    assert macs == w.work((64, 64 * 16384), cfg)[0] / 2
    assert macs > w.work((64, 64 * 15360), cfg)[0] / 2
    assert 12.5e9 < macs / 64 < 12.8e9


def test_per_layer_readers_on_a_v5e_reading():
    """The step's share of the peak reads the window's calls; the
    adder's share of busy time its kernel's operations; the expert
    counter per held expert, layer and step."""
    from chipbench.cells import load_module
    base = os.path.join(REPO, "chipbench", "metrics")

    def reader(name):
        return load_module(os.path.join(base, name + ".py"),
                           "test_metric_" + name).read

    calls = [(64, 64 * 12000)] * 50
    add = [trace_lib.Op(f"approx_add.{i}", "approx_add", 10_000 * i,
                        10_000 * i + 8_000) for i in range(1000)]
    r = _reading(add, calls, {"held_pairs": 50 * 8 * 4 * 2.4,
                              "steps": 50, "held_experts": 8,
                              "moe_layers": 4})
    mfu = reader("mfu." + CONFIG)(r)
    from chipbench.readings import work_of
    least = 50 * peaks.bound_seconds(*work_of("deepseek_v2_decode")(
        calls[0], r.config), "TPU v5 lite", "bf16_flops")[0]
    assert mfu == pytest.approx(100 * least / 1.0)
    assert 0 < mfu < 100
    assert reader("expert_tokens.dsv2")(r) == pytest.approx(2.4)
    assert reader("idle_share.dsv2")(r) == pytest.approx(100 * (1 - 8e-3))
    # the adder's 8 ms of the 8 ms busy, then beside 24 ms of other work
    assert reader("approx_add_share.dsv2")(r) == pytest.approx(100.0)
    r.trace.devices[DEV] = add + [trace_lib.Op("fusion.1", "", 10_000_000,
                                               34_000_000)]
    assert reader("approx_add_share.dsv2")(r) == pytest.approx(25.0)
    r.trace.devices[DEV] = []
    assert reader("approx_add_share.dsv2")(r) is None
    r.counters = {}
    assert reader("expert_tokens.dsv2")(r) is None

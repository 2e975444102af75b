"""The ``mla_decode`` loop: greedy decode at a fixed batch over long
documents whose latent caches were built by prefill in set-up.  Its
end-to-end value is ``gmac_per_s``: the multiply-accumulates of a step
at its compiled shape (``work/deepseek_v2_decode.step_macs``: the batch's
tokens through the weights, attention over all ``ctx`` slots of every
row) for every decode step of the window, over the window's seconds.
That count is the same for every step, so ``gmac_per_s`` is the tokens
per second (``tok_per_s``, also a counter: one token per sequence a
step) times a constant of the configuration and the mix.

Set-up draws ``docs`` documents (lengths uniform in [``doc_min``,
``doc_max``], token ids uniform over the vocabulary), prefills each once
at the one shape (1, ``ctx``) with the tail padded, and places each
document's cache in ``asks`` of the ``batch`` slots: slot s asks
document ``s // asks``.  Each slot starts its answer at the document's
length with a seeded first token.  The window is a closed loop of decode
steps: the argmax is fed back on the device, each row's position
advances by one, and after ``answer`` answer tokens a slot starts a new
answer over its document (its position rewinds to the document's length
and it takes its next seeded first token).  At most ``ahead`` steps are
in flight.

The weights are drawn from the seed by their published names and in
their published layout (:class:`Weights`, independent of the program):
the system maps them into the program's parameters, and the reference
reads them by name.  The step returns its blocks' intermediates beside
the logits.

The check takes ``samples`` seeded (step, slot) pairs of the window,
each slot asking another document.  Against the plain reference:

* ``logit_err``: the largest absolute difference between the logits of
  the sampled step at the slot and the reference's full forward pass
  over the document and the slot's answer so far, over the reference
  logits' standard deviation (``logit_rms_err``, the root mean square of
  the same, has no limit).  The adder turns bf16 rounding into whole low
  bits of every later layer's input, so this bound is loose.
* ``attn0_err``: the same measure of layer 0's attention output at the
  slot, which reads the step's cache and comes before any residual add.
* ``add_bad``: elements of every residual add of the sampled steps, all
  rows, where the step's result differs from the configured adder on
  the step's own operands.  Exact.
* ``route_off``: the share of (row, MoE layer) pairs of the sampled steps
  whose six experts differ from the reference's routing of the step's
  own input to that layer (rounding moves a few near ties).
* ``moe_err``: the largest relative difference (2-norm of a row) of an
  MoE layer's output from the reference's held and shared experts on the
  step's own input with the step's own routing, over those pairs.

The limits are the configuration's ``<name>_limit``.
"""

from __future__ import annotations

import collections
import functools
import os
import time
from collections.abc import Mapping
from typing import Dict

import numpy as np

from chipbench.cells import load_module
from chipbench.loops import Loop, Reservoir, Spans, _rngs

#: The step's work counts, which the ``mfu`` reader reads too.
_WORK = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))), "work", "deepseek_v2_decode.py")


def published_shapes(cfg: dict) -> dict:
    """Every weight of this chip's share by its published name: (shape in
    the published ``(out, in)`` layout, fan-in scale, or None for a norm
    weight)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    h, r, qr = (cfg["num_attention_heads"], cfg["kv_lora_rank"],
                cfg["q_lora_rank"])
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    first, count = cfg["deployment"]["held"]
    fm = cfg["moe_intermediate_size"]
    out = {"model.embed_tokens.weight": ((v, d), 1.0)}

    def lin(name, o, i):
        out[name] = ((o, i), i ** -0.5)

    def swiglu(pre, width):
        lin(pre + "gate_proj.weight", width, d)
        lin(pre + "up_proj.weight", width, d)
        lin(pre + "down_proj.weight", d, width)

    for i in range(cfg["num_hidden_layers"]):
        pre = f"model.layers.{i}."
        att = pre + "self_attn."
        out[pre + "input_layernorm.weight"] = ((d,), None)
        lin(att + "q_a_proj.weight", qr, d)
        out[att + "q_a_layernorm.weight"] = ((qr,), None)
        lin(att + "q_b_proj.weight", h * (dn + dr), qr)
        lin(att + "kv_a_proj_with_mqa.weight", r + dr, d)
        out[att + "kv_a_layernorm.weight"] = ((r,), None)
        lin(att + "kv_b_proj.weight", h * (dn + dv), r)
        lin(att + "o_proj.weight", d, h * dv)
        out[pre + "post_attention_layernorm.weight"] = ((d,), None)
        if i < cfg["first_k_dense_replace"]:
            swiglu(pre + "mlp.", cfg["intermediate_size"])
            continue
        lin(pre + "mlp.gate.weight", cfg["deployment"]["n_routed_experts"],
            d)
        for e in range(first, first + count):
            swiglu(f"{pre}mlp.experts.{e}.", fm)
        swiglu(pre + "mlp.shared_experts.", cfg["n_shared_experts"] * fm)
    out["model.norm.weight"] = ((d,), None)
    lin("lm_head.weight", v, d)
    return out


@functools.lru_cache(maxsize=None)
def _drawer(shape, scale, dtype):
    import jax
    import jax.numpy as jnp

    def draw(seed, index):
        z = jax.random.normal(jax.random.fold_in(jax.random.key(seed),
                                                 index), shape, jnp.float32)
        w = 1.0 + 0.1 * z if scale is None else z * scale
        return w.astype(dtype)

    return jax.jit(draw)


class Weights(Mapping):
    """The seeded weights by published name, each drawn on the device
    when it is read (the same name and seed give the same values):
    normal at fan-in scale, the embedding at unit scale, norm weights
    1 + N(0, 0.1^2)."""

    def __init__(self, cfg: dict, seed: int):
        self.shapes = published_shapes(cfg)
        self.index = {name: i for i, name in enumerate(self.shapes)}
        self.seed, self.dtype = seed, cfg["dtype"]

    def __getitem__(self, name):
        shape, scale = self.shapes[name]
        return _drawer(shape, scale, self.dtype)(self.seed, self.index[name])

    def __iter__(self):
        return iter(self.shapes)

    def __len__(self):
        return len(self.shapes)


class MlaDecodeLoop(Loop):

    def __init__(self, system, mix: dict, seed: int, spans: Spans,
                 name: str):
        super().__init__()
        self.system, self.mix, self.spans = system, mix, spans
        if mix["docs"] * mix["asks"] != mix["batch"]:
            raise ValueError("docs * asks must equal batch")
        if mix["doc_max"] + mix["answer"] > mix["ctx"]:
            raise ValueError("a document and its answer must fit ctx")
        rin, _, rsample = _rngs(seed)
        self.rsample = rsample
        vocab = system.vocab
        self.lengths = rin.integers(mix["doc_min"], mix["doc_max"] + 1,
                                    mix["docs"])
        docs = rin.integers(0, vocab, (mix["docs"], mix["ctx"]),
                            dtype=np.int32)
        docs[np.arange(mix["ctx"])[None, :] >= self.lengths[:, None]] = 0
        self.docs = docs
        self.firsts = rin.integers(0, vocab, (mix["batch"], mix["restarts"]),
                                   dtype=np.int32)
        self.weight_seed = int(rin.integers(0, 2 ** 31))
        self.weights = self.params = None
        self.slot_doc = np.arange(mix["batch"]) // mix["asks"]
        self.sample = Reservoir(mix["samples"], rsample)
        self._picked = None
        self.cache = None

    # ------------------------------------------------------------ set-up

    def _advance_fn(self):
        """The loop's own bookkeeping after a step, on the device."""
        import jax
        import jax.numpy as jnp
        answer, restarts = self.mix["answer"], self.mix["restarts"]
        firsts = jnp.asarray(self.firsts)
        doc_len = jnp.asarray(self.lengths[self.slot_doc], jnp.int32)

        def advance(state, logits, held):
            tok, pos, n, again, answers, held_sum = state
            rows = jnp.arange(tok.shape[0])
            answers = answers.at[rows, n].set(tok)
            nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
            done = n + 1 >= answer
            again = again + done
            tok = jnp.where(done, firsts[rows, again % restarts], nxt)
            pos = jnp.where(done, doc_len, pos + 1)
            n = jnp.where(done, 0, n + 1)
            return (tok, pos, n, again, answers, held_sum + held)

        return jax.jit(advance)

    def _place_fn(self):
        """Copies one document's prefilled cache into its slots."""
        import jax

        def place(cache, one, slots):
            def put(big, small):
                axis = big.ndim - 3          # the batch axis
                idx = (slice(None),) * axis + (slots,)
                return big.at[idx].set(
                    jax.numpy.broadcast_to(small, big[idx].shape).astype(
                        big.dtype))
            return jax.tree.map(put, cache, one)

        return jax.jit(place, donate_argnums=0)

    def setup(self) -> None:
        import jax
        import jax.numpy as jnp
        mix, sysm = self.mix, self.system
        self.weights = Weights(sysm.config, self.weight_seed)
        self.params = sysm.init_params(self.weights)
        prefill = sysm.prefill(mix["ctx"])
        caches = []
        for d in range(mix["docs"]):
            _, one, _ = prefill(self.params, {"tokens": jnp.asarray(
                self.docs[d:d + 1])})
            caches.append(one)
        jax.block_until_ready(caches)
        self.cache = sysm.init_cache(mix["batch"], mix["ctx"])
        place = self._place_fn()
        for d, one in enumerate(caches):
            slots = jnp.asarray(np.flatnonzero(self.slot_doc == d))
            self.cache = place(self.cache, one, slots)
        del caches
        b = mix["batch"]
        self.state = (jnp.asarray(self.firsts[:, 0]),
                      jnp.asarray(self.lengths[self.slot_doc], jnp.int32),
                      jnp.zeros(b, jnp.int32), jnp.zeros(b, jnp.int32),
                      jnp.zeros((b, mix["answer"]), jnp.int32),
                      jnp.zeros((), jnp.int32))
        self.advance = self._advance_fn()
        for _ in range(2):            # compile and warm both programs
            self._step()
        jax.block_until_ready(self.state)

    def _step(self):
        """One decode step; (its logits, the positions and answer
        indices it decoded at, the answers with its tokens written, its
        stats)."""
        tok, pos, n = self.state[:3]
        with self.spans("model.decode"):
            logits, self.cache, stats = self.system.decode(
                self.params, tok[:, None], pos, self.cache)
        self.state = self.advance(self.state, logits, stats["held_pairs"])
        return logits, pos, n, self.state[4], stats

    # ------------------------------------------------------------ window

    def window(self, seconds: float) -> Dict[str, float]:
        import jax
        ahead = self.mix["ahead"]
        b = self.mix["batch"]
        pending: collections.deque = collections.deque()
        start = jax.device_get(self.state[1:4])   # pos, n, restarts
        held0 = int(self.state[5])
        t0 = time.perf_counter()
        t_end = t0 + seconds
        steps = 0
        while time.perf_counter() < t_end:
            item = self._step()
            self.sample.offer(item)
            pending.append(item[0])
            steps += 1
            if len(pending) > ahead:
                with self.spans("decode.wait"):
                    pending.popleft().block_until_ready()
        with self.spans("decode.wait"):
            jax.block_until_ready(self.state)
        elapsed = time.perf_counter() - t0
        held = int(self.state[5]) - held0
        self.attempted = steps * b
        self.calls = [(b, live) for live in self._live(start, steps)]
        macs = load_module(_WORK, "chipbench_work_deepseek_v2_decode"
                           ).step_macs(b, self.mix["ctx"], self.system.config)
        values = {"gmac_per_s": steps * macs / elapsed / 1e9,
                  "tok_per_s": steps * b / elapsed}
        self.counters = {
            "steps": steps, "window_s": elapsed, "held_pairs": held,
            "held_experts": self.system.held_experts,
            "moe_layers": self.system.moe_layers,
            "tok_per_s": values["tok_per_s"], "step_macs": macs}
        return values

    def _live(self, start, steps):
        """Context tokens attended per step, summed over the batch (each
        row attends its positions 0..pos)."""
        pos, n, _ = (np.asarray(a) for a in start)
        doc_len = self.lengths[self.slot_doc]
        out = []
        for _ in range(steps):
            out.append(int(np.sum(pos + 1)))
            done = n + 1 >= self.mix["answer"]
            pos = np.where(done, doc_len, pos + 1)
            n = np.where(done, 0, n + 1)
        return out

    # ------------------------------------------------------------- check

    def _samples(self):
        """Each sampled step with a slot of another document, drawn from
        the seed: the step's logits and layer 0's attention output at the
        slot, and every layer's intermediates at every row."""
        if self._picked is None:
            docs = self.rsample.permutation(self.mix["docs"])
            self._picked = []
            for i, item in enumerate(self.sample.items):
                d = int(docs[i % len(docs)])
                slot = d * self.mix["asks"] + int(
                    self.rsample.integers(self.mix["asks"]))
                layers = [{k: np.asarray(v) for k, v in t.items()}
                          for t in self.system.layer_taps(item[4])]
                self._picked.append({
                    "item": item[:4], "slot": slot, "layers": layers,
                    "logits": np.asarray(item[0][slot, -1], np.float32),
                    "attn0": layers[0]["mix"][slot].astype(np.float32)})
        return self._picked

    def _reference(self, ref, cfg, pick):
        _, pos, n, answers = pick["item"]
        slot = pick["slot"]
        pos, n = int(pos[slot]), int(n[slot])
        d = self.slot_doc[slot]
        length = int(self.lengths[d])
        if pos != length + n:
            raise RuntimeError(f"slot {slot}: position {pos} is not "
                               f"{length} + {n}")
        tokens = self.docs[d].copy()
        tokens[length:pos + 1] = np.asarray(answers[slot, :n + 1])
        want = ref.reference(self.weights, tokens, pos + 1, cfg)
        return {k: np.asarray(v, np.float32) for k, v in want.items()}

    def _free_device(self):
        """The cache and the parameters make room for the reference."""
        import jax
        for tree in (self.cache, self.params):
            for leaf in jax.tree.leaves(tree):
                leaf.delete()
        self.cache = self.params = None

    def check(self, ref, cfg) -> Dict[str, tuple]:
        picks = self._samples()
        if self.cache is not None:
            self._free_device()
        worst = {k: 0.0 if picks else float("inf")
                 for k in ("logit_err", "logit_rms_err", "attn0_err",
                           "moe_err")}
        bad = compared = rows = off = 0
        first = cfg["first_k_dense_replace"]
        for pick in picks:
            want = self._reference(ref, cfg, pick)
            largest, mean = _err(pick["logits"], want["logits"])
            worst["logit_err"] = max(worst["logit_err"], largest)
            worst["logit_rms_err"] = max(worst["logit_rms_err"], mean)
            worst["attn0_err"] = max(worst["attn0_err"],
                                     _err(pick["attn0"], want["attn0"])[0])
            for i, t in enumerate(pick["layers"]):
                for a, b, got in (("x", "mix", "mid"), ("mid", "out",
                                                        "next")):
                    s = np.asarray(ref.residual_add(t[a], t[b],
                                                    cfg["adder"]))
                    bad += int(np.sum(s != t[got]))
                    compared += s.size
                if i < first:
                    continue
                out, (gates, ids) = ref.moe_layer(
                    self.weights, i, t["mid"], t["gates"], t["ids"], cfg)
                out = np.asarray(out)
                worst["moe_err"] = max(worst["moe_err"], float(np.max(
                    np.linalg.norm(t["out"] - out, axis=-1)
                    / np.linalg.norm(out, axis=-1))))
                same = np.all(np.sort(np.asarray(ids), -1)
                              == np.sort(t["ids"], -1), -1)
                off += int(np.sum(~same))
                rows += same.size
        worst["route_off"] = off / rows if rows else float("inf")
        checks = {k: (v, cfg[k + "_limit"]) for k, v in worst.items()
                  if k != "logit_rms_err"}
        checks["add_bad"] = (bad, cfg["add_bad_limit"])
        checks["logit_rms_err"] = (worst["logit_rms_err"], None)
        checks["compared_logits"] = (sum(p["logits"].size for p in picks),
                                     None)
        checks["compared_adds"] = (compared, None)
        checks["compared_moe_rows"] = (rows, None)
        return checks

    def substitute(self, ref, cfg) -> None:
        """The reference under ``cfg`` stands in for the step: its logits
        and layer 0's attention output; in each layer, from the step's
        input and attention output, ``cfg``'s adds and the reference's
        MoE with its own routing."""
        picks = self._samples()
        self._free_device()
        first = cfg["first_k_dense_replace"]
        for pick in picks:
            want = self._reference(ref, cfg, pick)
            pick["logits"], pick["attn0"] = want["logits"], want["attn0"]
            for i, t in enumerate(pick["layers"]):
                t["mid"] = np.asarray(ref.residual_add(t["x"], t["mix"],
                                                       cfg["adder"]))
                if i >= first:
                    _, (gates, ids) = ref.moe_layer(
                        self.weights, i, t["mid"], t["gates"], t["ids"],
                        cfg)
                    out, _ = ref.moe_layer(self.weights, i, t["mid"], gates,
                                           ids, cfg)
                    t.update(out=np.asarray(out), gates=np.asarray(gates),
                             ids=np.asarray(ids))
                t["next"] = np.asarray(ref.residual_add(t["mid"], t["out"],
                                                        cfg["adder"]))


def _err(got, want):
    """The largest and the root-mean-square absolute difference, each
    over the reference's standard deviation; infinite for a mis-shaped
    or non-finite output."""
    if got.shape != want.shape or not np.all(np.isfinite(got)):
        return float("inf"), float("inf")
    d = np.abs(got - want) / np.std(want)
    return float(d.max()), float(np.sqrt(np.mean(d * d)))


LOOP = MlaDecodeLoop

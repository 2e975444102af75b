"""A run whose timed path is broken underneath must come out not
correct: an answer altered where it is produced, and half of a batch
left out.  The runs skip the look for a chip and drive the rest of the
harness on the CPU."""

from __future__ import annotations

import numpy as np
import pytest

from chipbench_testutil import bench_copy, run_cell


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return bench_copy(tmp_path_factory.mktemp("faults"))


def _alter_one(fn):
    """Every output with one element changed where it is produced."""
    def call(*args):
        out = np.array(fn(*args))
        flat = out.reshape(-1)
        flat[flat.size // 2] ^= 1
        return out
    return call


def _half_batch(fn):
    """Outputs for the first half of each batch only."""
    def call(batch):
        out = fn(batch)
        return out[: max(len(batch) // 2, 1)]
    return call


def _half_rows(fn):
    """A GEMM that leaves out the second half of its rows."""
    def call(a, b):
        out = np.array(fn(a, b))
        out[out.shape[0] // 2:] = 0
        return out
    return call


def _as_device(fn):
    def call(*args):
        import jax.numpy as jnp
        return jnp.asarray(fn(*args))
    return call


FAULTS = [
    ("img1024-bsd-stream", "answer_altered", _alter_one),
    ("img1024-bsd-stream", "half_batch", _half_batch),
    ("gemm-r50-trunc8", "answer_altered",
     lambda f: _as_device(_alter_one(f))),
    ("gemm-r50-trunc8", "half_batch", lambda f: _as_device(_half_rows(f))),
]


@pytest.mark.parametrize("cell,fault,patch", FAULTS,
                         ids=[f"{c}-{f}" for c, f, _ in FAULTS])
def test_broken_timed_path_is_not_correct(checkout, cell, fault, patch):
    res = run_cell(checkout, cell, patch=patch)
    assert res["correct"] is False
    assert any(c["limit"] == 0 and c["value"] > 0
               for c in res["checks"].values())


def test_subtle_adder_fault_is_caught(checkout, monkeypatch):
    """HALOC-AxA with its bit m-1 XOR-merged instead of OR-merged (the
    one-case variant the paper's truth table rules out) differs from
    the reference somewhere in a run's sample."""
    import jax
    from repro.ax import backends

    def xor_merge(a, b, spec, fast=False):
        m, k = spec.lsm_bits, spec.const_bits
        bit = lambda x, i: (x >> i) & 1  # noqa: E731
        s_m1 = (bit(a, m - 1) ^ bit(b, m - 1)) ^ (bit(a, m - 2)
                                                  & bit(b, m - 2))
        low = (s_m1 << (m - 1)) | ((bit(a, m - 2) ^ bit(b, m - 2))
                                   << (m - 2)) \
            | ((a | b) & (((1 << (m - 2)) - 1) ^ ((1 << k) - 1))) \
            | ((1 << k) - 1)
        high = (a >> m) + (b >> m) + (bit(a, m - 1) & bit(b, m - 1))
        return ((high << m) | low) & ((1 << spec.n_bits) - 1)

    def patch(pipe):
        def call(batch):
            with jax.disable_jit():
                return pipe.chain(np.asarray(batch))
        return call

    assert run_cell(checkout, "img1024-bsd-stream", patch=patch)["correct"]
    monkeypatch.setattr(backends, "approx_add_mod", xor_merge)
    res = run_cell(checkout, "img1024-bsd-stream", patch=patch)
    assert res["correct"] is False
    assert res["checks"]["bad_px"]["value"] > 0

"""CPU rehearsals of every cell at test sizes: each mix's generator, the
metric arithmetic, the comparison with the plain reference and its
control, and the refusal to run without a TPU."""

from __future__ import annotations

import json
import os
import subprocess
import sys

import pytest

from chipbench_testutil import REPO, bench_copy, run_cell

CELLS = ("img1024-bsd-stream", "gemm-r50-trunc8")

E2E = {"img1024-bsd-stream": "mpix_per_s", "gemm-r50-trunc8": "gmac_per_s"}


@pytest.fixture(scope="module")
def checkout(tmp_path_factory):
    return bench_copy(tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("cell", CELLS)
def test_cell_end_to_end_run_is_correct(checkout, cell):
    res = run_cell(checkout, cell)
    assert res["correct"] is True
    assert set(res["metrics"]) == {E2E[cell], "setup_s"}
    assert res["metrics"][E2E[cell]]["value"] > 0
    assert res["attempted"] > 0
    assert list(res)[-1] == "checks"
    assert all(c["limit"] == 0 for c in res["checks"].values())
    assert [k for k in res["counters"] if k.startswith("compared_")]


@pytest.mark.parametrize("cell", CELLS)
def test_cell_window_compiles_nothing(checkout, cell):
    counters = run_cell(checkout, cell, seed=2**31 + 23)["counters"]
    assert counters["window_traces"] == 0
    assert counters["window_compiles"] == 0


def test_compile_counter_sees_a_compile_only_while_armed():
    import jax
    import jax.numpy as jnp
    from chipbench.run import CompileCounter
    counter = CompileCounter()
    x = jnp.arange(7)
    jax.jit(lambda v: v * 5 - 2)(x).block_until_ready()
    assert counter.counts == {"traces": 0, "compiles": 0}
    counter.armed = True
    jax.jit(lambda v: v * 7 + 3)(x).block_until_ready()
    assert counter.counts["traces"] >= 1
    assert counter.counts["compiles"] >= 1


@pytest.mark.parametrize("cell", CELLS)
def test_cell_control_fails_the_comparison(checkout, cell):
    res = run_cell(checkout, cell, control=1)
    assert res["correct"] is False
    bad = [c["value"] for c in res["checks"].values()
           if c["limit"] == 0 and c["value"] > 0]
    assert bad


@pytest.mark.parametrize("cell", CELLS)
def test_cell_traced_run_reports_per_layer_metrics(checkout, cell):
    res = run_cell(checkout, cell, trace=1)
    assert res["correct"] is True
    assert "setup_s" not in res["metrics"]
    assert res["metrics"], "a traced run reports per-layer metrics"
    assert res["device"]["window_s"] > 0
    assert 0 < res["device"]["busy_s"] <= res["device"]["window_s"]
    assert len(res["breakdown"]["device_ops"]) <= 10
    assert len(res["breakdown"]["idle_gaps"]) <= 10


def test_stream_cell_on_the_pallas_interpreter(checkout):
    res = run_cell(checkout, "img1024-bsd-stream", backend="pallas")
    assert res["correct"] is True


def _command(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.update(env_extra or {})
    return subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "img1024-bsd-stream", "--seed", "5", "--seconds", "1",
         "--trace", "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def _has_result(stdout: str) -> bool:
    for line in stdout.splitlines():
        try:
            if isinstance(json.loads(line), dict):
                return True
        except ValueError:
            pass
    return False


def test_command_refuses_a_host_without_a_tpu():
    proc = _command(REPO)
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)
    assert "not a TPU" in proc.stderr


def test_command_refuses_a_directory_without_the_program(tmp_path):
    import shutil
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    tmp_path / "chipbench")
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    proc = _command(str(tmp_path))
    assert proc.returncode != 0
    assert not _has_result(proc.stdout)

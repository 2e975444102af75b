"""A copy of the benchmark at test sizes, for CPU rehearsals.

The copy holds ``chipbench/`` and a ``BENCHMARK.json`` next to a link
to the program's ``src/``; each mix there is shrunk to the ``test_size``
parameters its own file carries (a few small images or GEMMs), which a
run never reads.  The harness finds every piece by name there, exactly
as in a checkout.
"""

from __future__ import annotations

import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def bench_copy(tmp_path, workloads=None) -> str:
    """A test-size benchmark checkout under ``tmp_path``; its root."""
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(REPO, "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    os.symlink(os.path.join(REPO, "src"), os.path.join(root, "src"))
    mixes = os.path.join(root, "chipbench", "mixes")
    for name in os.listdir(mixes):
        path = os.path.join(mixes, name)
        with open(path) as f:
            mix = json.load(f)
        if "test_size" in mix:
            mix.update(mix.pop("test_size"))
            with open(path, "w") as f:
                json.dump(mix, f)
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        bench = json.load(f)
    if workloads is not None:
        bench["workloads"] = [w for w in bench["workloads"]
                              if w["name"] in workloads]
    write_bench(root, bench)
    return root


def read_bench(root: str) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def write_bench(root: str, bench: dict) -> None:
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f, indent=1)


def run_cell(root, workload, *, seed=2**31 + 11, seconds=None, trace=0,
             control=0, backend="jax", patch=None, keep_trace=None):
    """One in-process run of ``workload`` on the CPU; the result."""
    from chipbench import run as run_lib
    if seconds is None:
        seconds = 0.3 if trace else 0.6
    args = run_lib.parse(["--workload", workload, "--seed", str(seed),
                          "--seconds", str(seconds), "--trace", str(trace),
                          "--control", str(control)]
                         + (["--keep-trace", keep_trace] if keep_trace else []))
    return run_lib.run(args, root=root, require_tpu=False, backend=backend,
                       patch=patch)

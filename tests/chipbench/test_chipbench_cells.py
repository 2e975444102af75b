"""The harness is driven by data: a configuration, a traffic mix and a
per-layer metric added as new files, with new ``BENCHMARK.json``
entries, run without an edit to any file that was there; so does a cell
that brings its own system and loop files (``data/grown/``, a toy
decoder reporting ``tok_per_s``)."""

from __future__ import annotations

import json
import os
import shutil

import pytest

from chipbench_testutil import bench_copy, read_bench, run_cell, \
    write_bench

GROWN = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "grown")


def _add_files(root: str) -> None:
    base = os.path.join(root, "chipbench")
    with open(os.path.join(base, "configs", "haloc16-img.json")) as f:
        cfg = json.load(f)
    cfg["name"] = "haloc16m6-img"
    cfg["adder"] = {"kind": "haloc_axa", "n_bits": 16, "lsm_bits": 6,
                    "const_bits": 3}
    with open(os.path.join(base, "configs", "haloc16m6-img.json"),
              "w") as f:
        json.dump(cfg, f)
    shutil.copy(os.path.join(base, "configs", "haloc16-img.py"),
                os.path.join(base, "configs", "haloc16m6-img.py"))
    with open(os.path.join(base, "mixes", "stream-b2-48.json"), "w") as f:
        json.dump({"loop": "stream", "batch": 2, "size": 48, "pool": 2,
                   "depth": 2, "chunk": 3, "sample": 2}, f)
    with open(os.path.join(base, "metrics", "batches.added.py"), "w") as f:
        f.write("def read(r):\n    return r.counters.get('batches')\n")
    bench = read_bench(root)
    bench["configs"].append({
        "name": "haloc16m6-img", "source": "https://arxiv.org/abs/2510.20137",
        "file": "chipbench/configs/haloc16m6-img.json", "reduced": [],
        "why": "a second (m, k) point of the same datapath"})
    bench["workloads"].append({
        "name": "img48-m6-stream", "config": "haloc16m6-img",
        "traffic": "stream-b2-48", "chips": 1, "why": "added by data"})
    for m in bench["end_to_end"]:
        if m["name"] == "mpix_per_s":
            m["workloads"].append("img48-m6-stream")
    bench["per_layer"].append({
        "name": "batches.added", "unit": "batches", "better": "higher",
        "source": "program_counter", "layer": "entry",
        "moves": "mpix_per_s", "workloads": ["img48-m6-stream"]})
    write_bench(root, bench)


def _add_toy_decode(root: str) -> None:
    """The files under ``data/grown/`` added to ``chipbench/``, none of
    them there before, and their entries to ``BENCHMARK.json``, the
    ``tok_per_s`` metric with its first cell among them."""
    for d, _, names in os.walk(GROWN):
        rel = os.path.relpath(d, GROWN)
        for n in names:
            if rel == "." and n == "entries.json" or n.endswith(".pyc"):
                continue
            dest = os.path.join(root, "chipbench", rel, n)
            assert not os.path.exists(dest), dest
            shutil.copy(os.path.join(d, n), dest)
    with open(os.path.join(GROWN, "entries.json")) as f:
        entries = json.load(f)
    bench = read_bench(root)
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        bench[key] += entries[key]
    write_bench(root, bench)


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    root = bench_copy(tmp_path_factory.mktemp("grown"),
                      workloads={"img1024-bsd-stream"})
    before = {p: open(p, "rb").read() for p in _files(root)}
    _add_files(root)
    _add_toy_decode(root)
    return root, before


def _files(root):
    out = []
    for d, _, names in os.walk(os.path.join(root, "chipbench")):
        if "__pycache__" not in d:
            out += [os.path.join(d, n) for n in names]
    return out


def test_added_files_change_no_existing_file(grown):
    root, before = grown
    for path, data in before.items():
        with open(path, "rb") as f:
            assert f.read() == data, path


def test_added_cell_runs_and_compares(grown):
    root, _ = grown
    res = run_cell(root, "img48-m6-stream")
    assert res["correct"] is True
    assert set(res["metrics"]) == {"mpix_per_s", "setup_s"}


def test_added_metric_is_reported_in_its_cell_only(grown):
    root, _ = grown
    res = run_cell(root, "img48-m6-stream", trace=1)
    assert res["metrics"]["batches.added"]["value"] > 0
    other = run_cell(root, "img1024-bsd-stream", trace=1)
    assert "batches.added" not in other["metrics"]


def test_added_config_is_held_to_its_own_reference(grown):
    """The new adder point's control (the exact adder) fails too."""
    root, _ = grown
    assert run_cell(root, "img48-m6-stream", control=1)["correct"] is False


def test_unknown_cell_is_refused(grown):
    from chipbench import cells
    with pytest.raises(cells.CellError):
        cells.resolve(grown[0], "no-such-cell")


def test_added_system_and_loop_cell_runs_and_compares(grown):
    res = run_cell(grown[0], "toy-decode")
    assert res["correct"] is True
    assert set(res["metrics"]) == {"tok_per_s", "setup_s"}
    assert res["metrics"]["tok_per_s"]["unit"] == "tok/s"
    steps = res["counters"]["steps"]
    assert steps > 0 and res["attempted"] == 8 * steps
    assert res["metrics"]["tok_per_s"]["value"] == pytest.approx(
        8 * steps / res["counters"]["window_s"])
    assert res["checks"] == {"bad_tok": {"value": 0, "limit": 0}}
    assert res["counters"]["compared_tok"] == 3 * 8


def test_added_loop_reports_its_metrics_when_traced(grown):
    """The step counter is read; the step's share of the peak reads
    nothing off the chips of the peak table, and is left out."""
    res = run_cell(grown[0], "toy-decode", trace=1)
    assert res["correct"] is True
    assert res["metrics"]["decode_steps.toy"]["value"] > 0
    assert "mfu.toy-step" not in res["metrics"]
    assert "tok_per_s" not in res["metrics"]


def _token_altered(step):
    return lambda tokens: step(tokens).at[0].add(1)


def _state_unchanged(step):
    return lambda tokens: tokens + 0


@pytest.mark.parametrize("patch,control", [
    (None, 1), (_token_altered, 0), (_state_unchanged, 0)],
    ids=["control", "token_altered", "state_unchanged"])
def test_added_loop_holds_its_cell_to_the_reference(grown, patch, control):
    res = run_cell(grown[0], "toy-decode", patch=patch, control=control)
    assert res["correct"] is False
    assert res["checks"]["bad_tok"]["value"] > 0


@pytest.mark.parametrize("path,key,value", [
    ("configs/toy-step.json", "system", "no_such_system"),
    ("mixes/toy-decode-b8.json", "loop", "no_such_loop"),
    ("mixes/toy-decode-b8.json", "loop", "not_a_loop")])
def test_missing_system_or_loop_is_refused(grown, tmp_path, path, key,
                                           value):
    """Before any device work: ``cells.resolve`` raises."""
    from chipbench import cells
    root = str(tmp_path)
    shutil.copytree(os.path.join(grown[0], "chipbench"),
                    os.path.join(root, "chipbench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(grown[0], "BENCHMARK.json"), root)
    with open(os.path.join(root, "chipbench", "loop", "not_a_loop.py"),
              "w") as f:
        f.write("LOOP = dict\n")
    target = os.path.join(root, "chipbench", path)
    with open(target) as f:
        data = json.load(f)
    data[key] = value
    with open(target, "w") as f:
        json.dump(data, f)
    cells.resolve(grown[0], "toy-decode")
    with pytest.raises(cells.CellError, match=value):
        cells.resolve(root, "toy-decode")

"""The harness is driven by data: a configuration, a traffic mix and a
per-layer metric added as new files, with new ``BENCHMARK.json``
entries, run without an edit to any file that was there."""

from __future__ import annotations

import json
import os
import shutil

import pytest

from chipbench_testutil import bench_copy, read_bench, run_cell, \
    write_bench


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


@pytest.fixture(scope="module")
def grown(tmp_path_factory):
    root = bench_copy(tmp_path_factory.mktemp("grown"),
                      workloads={"img1024-bsd-stream"})
    before = {p: open(p, "rb").read() for p in _files(root)}
    _add_files(root)
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

"""Find a cell's pieces by the names ``BENCHMARK.json`` gives them.

A cell (an entry of ``workloads``) names a configuration and a traffic
mix.  Its files, all under ``chipbench/``, are
``configs/<config>.json`` (the configuration as it is run),
``configs/<config>.py`` (its plain reference),
``system/<system>.py`` for the configuration's ``system`` key (its
``build(cfg, backend)`` makes the system under test),
``mixes/<traffic>.json`` (the mix's parameters),
``loop/<loop>.py`` for the mix's ``loop`` key (its ``LOOP``, a
:class:`chipbench.loops.Loop`, is the generator) and
``metrics/<metric>.py`` for each per-layer metric the cell reports.
Adding a cell, a configuration, a system, a mix, a loop or a metric
adds files and entries; nothing here changes.  Every file is loaded
here, before any device work, and a missing one is a :class:`CellError`.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Dict, List

from chipbench import loops

PACKAGE = "chipbench"


class CellError(Exception):
    """The cell or one of its files is missing or malformed."""


def load_module(path: str, name: str):
    if not os.path.isfile(path):
        raise CellError(f"missing {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _load_json(path: str) -> dict:
    if not os.path.isfile(path):
        raise CellError(f"missing {path}")
    with open(path) as f:
        return json.load(f)


def _piece(base: str, kind: str, name, attr: str):
    """``attr`` of ``<base>/<kind>/<name>.py``."""
    if not isinstance(name, str):
        raise CellError(f"no {kind} named: {name!r}")
    module = load_module(os.path.join(base, kind, name + ".py"),
                         f"{PACKAGE}_{kind}_{name}")
    if not hasattr(module, attr):
        raise CellError(f"{kind}/{name}.py defines no {attr}")
    return getattr(module, attr)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    reference: object          # the config's plain reference module
    system: object             # build(cfg, backend) of the config's system
    mix: dict
    loop: type                 # the mix's loops.Loop subclass
    end_to_end: List[dict]     # the BENCHMARK.json entries it reports
    per_layer: List[dict]
    readers: Dict[str, object]  # per-layer metric name -> reader module


def _reports(metric: dict, cell: str, e2e_names=None) -> bool:
    """Whether ``cell`` reports ``metric``: the cells its ``workloads``
    key lists; without one, every cell (an end-to-end metric) or every
    cell that reports the metric it ``moves`` (a per-layer metric)."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def resolve(root: str, workload: str) -> Cell:
    """The cell ``workload`` of ``<root>/BENCHMARK.json``."""
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise CellError(f"no workload {workload!r} in BENCHMARK.json; "
                        f"known: {sorted(cells)}")
    w = cells[workload]
    base = os.path.join(root, PACKAGE)
    config = _load_json(os.path.join(base, "configs", w["config"] + ".json"))
    reference = load_module(os.path.join(base, "configs",
                                         w["config"] + ".py"),
                            f"{PACKAGE}_ref_{w['config']}")
    system = _piece(base, "system", config.get("system"), "build")
    mix = _load_json(os.path.join(base, "mixes", w["traffic"] + ".json"))
    loop = _piece(base, "loop", mix.get("loop"), "LOOP")
    if not (isinstance(loop, type) and issubclass(loop, loops.Loop)):
        raise CellError(f"loop/{mix['loop']}.py: LOOP is not a Loop")
    e2e = [m for m in bench["end_to_end"] if _reports(m, workload)]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if _reports(m, workload, names)]
    readers = {m["name"]: load_module(
        os.path.join(base, "metrics", m["name"] + ".py"),
        f"{PACKAGE}_metric_{m['name']}") for m in layer}
    return Cell(name=workload, chips=int(w["chips"]), config=config,
                reference=reference, system=system, mix=mix, loop=loop,
                end_to_end=e2e, per_layer=layer, readers=readers)

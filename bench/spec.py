"""Find a cell's files by the names ``BENCHMARK.json`` gives them.

A cell (one entry of ``workloads``) names a configuration and a traffic
mix.  Its files are:

* ``bench/configs/<config>.json`` (the path is the config entry's
  ``file``): the model's sizes, the deployment and the reference;
* ``bench/traffic/<traffic>.json``: the mix's parameters, with rates as
  multiples of the cell's knee;
* ``bench/cells/<workload>.json``: the cell's knee rate and the limits of
  its correctness checks.

The configuration names two modules of its own: its model family
(``bench/families/<family>.py``, ``"dense"`` where it names none) and its
plain reference (its ``"reference"`` path).  Both are looked for first
under the root the cell was loaded from, then in this checkout.

The end-to-end and per-layer metrics that apply to the cell are taken
from ``BENCHMARK.json`` by the rules of its ``workloads`` keys.
"""

from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import pathlib
import sys
from typing import Dict, List

ROOT = pathlib.Path(__file__).resolve().parents[1]


def read_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """Everything one run of a cell needs, read from data files."""
    name: str
    chips: int
    config: dict
    traffic: dict
    rates: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    #: the directory the cell's files were read from
    root: pathlib.Path = ROOT

    @property
    def knee_rps(self) -> float:
        return float(self.rates["knee_rps"])

    @property
    def limits(self) -> Dict[str, float]:
        return self.rates["limits"]


@functools.lru_cache(maxsize=None)
def _exec(path: pathlib.Path):
    name = f"bench:{path}"
    mod = importlib.util.module_from_spec(
        importlib.util.spec_from_file_location(name, path))
    sys.modules[name] = mod
    mod.__spec__.loader.exec_module(mod)
    return mod


@functools.lru_cache(maxsize=None)
def load_module(root: pathlib.Path, relpath: str):
    """The Python module at ``root/relpath``, or at ``ROOT/relpath`` where
    ``root`` has none; each file is executed once per process.  Raises
    ``FileNotFoundError`` naming every path it looked for."""
    tried = []
    for base in dict.fromkeys((pathlib.Path(root).resolve(), ROOT)):
        path = base / relpath
        if path.is_file():
            return _exec(path)
        tried.append(str(path))
    raise FileNotFoundError(f"no module {relpath!r}: looked for "
                            f"{' and '.join(tried)}")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``name`` of ``root/BENCHMARK.json`` with its files.
    Raises ``KeyError`` for a cell the benchmark does not have."""
    bm = read_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    config = read_json(root / configs[w["config"]]["file"])
    traffic = read_json(root / "bench" / "traffic" / f"{w['traffic']}.json")
    rates = read_json(root / "bench" / "cells" / f"{name}.json")
    e2e = [m for m in bm["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    layer = [m for m in bm["per_layer"]
             if _applies(m, name) and m["moves"] in reported]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, rates=rates, end_to_end=e2e,
                per_layer=layer, root=pathlib.Path(root))

"""Finds what a cell needs by the names in ``BENCHMARK.json``: the
configuration (``configs/<config>.json`` and its work file
``configs/<config>.py``), the traffic mix (``traffic/<traffic>.json``),
the reference (``reference/<reference>.py``) and one reader per
per-layer metric (``metrics/<metric>.py``).  Adding a cell, a mix, a
configuration or a metric adds files and entries; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Any, Dict, List

from lib.traffic import Traffic

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(path: str) -> Dict[str, Any]:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    root: str                 # the checkout: BENCHMARK.json and bench/
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Traffic
    work: Any                 # the configuration's work module
    reference: Any            # the configuration's reference module
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]

    def reader(self, metric: str):
        return load_module(os.path.join(self.root, "bench", "metrics", f"{metric}.py"),
                           f"bench_metric_{metric.replace('.', '_')}")


def _applies(metric: Dict[str, Any], cell: str, e2e_names=None) -> bool:
    """A metric with ``workloads`` applies to those cells; without, an
    end-to-end metric applies everywhere and a per-layer metric wherever
    the end-to-end metric it moves is reported."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return e2e_names is None or metric["moves"] in e2e_names


def load_cell(workload: str, root: str = ROOT) -> Cell:
    bench = os.path.join(root, "bench")
    bm = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in bm["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json; known: {sorted(cells)}")
    w = cells[workload]
    configs = {c["name"]: c for c in bm["configs"]}
    config = _json(os.path.join(root, configs[w["config"]]["file"]))
    traffic = Traffic.from_dict(_json(os.path.join(bench, "traffic", f"{w['traffic']}.json")),
                                where=f"traffic/{w['traffic']}.json")
    work = load_module(os.path.join(bench, "configs", f"{config['name']}.py"),
                       f"bench_work_{config['name'].replace('-', '_').replace('.', '_')}")
    reference = load_module(os.path.join(bench, "reference", f"{config['reference']}.py"),
                            f"bench_reference_{config['reference']}")
    e2e = [m for m in bm["end_to_end"] if _applies(m, workload)]
    names = [m["name"] for m in e2e]
    per_layer = [m for m in bm["per_layer"] if _applies(m, workload, names)]
    return Cell(root, workload, int(w["chips"]), config, traffic, work, reference, e2e,
                per_layer)

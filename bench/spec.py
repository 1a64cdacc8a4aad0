"""Find a cell's parts by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix. The configuration's file
is the one ``BENCHMARK.json`` gives; the mix is ``bench/traffic/<mix>.json``
and names its driver, the module ``bench.drivers.<driver>``. A per-layer
metric is read by ``bench/layer_metrics/<metric>.py``, loaded by path
because metric names hold dots. Adding a cell, a mix or a
metric adds files and entries; no file here changes.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import os
from types import ModuleType
from typing import List

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def load_module(path: str, name: str) -> ModuleType:
    """Import one file by path (metric names hold dots, so no import
    statement can name them)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or spec.loader is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict                 # the configuration file, as run
    traffic_name: str
    traffic: dict                # the mix file
    end_to_end: List[dict]       # metrics this cell reports with --trace 0
    per_layer: List[dict]        # metrics this cell reports with --trace 1

    def driver(self) -> ModuleType:
        return importlib.import_module(f"bench.drivers.{self.traffic['driver']}")


def reports(metric: dict, cell: str) -> bool:
    """Whether ``cell`` reports ``metric`` (no ``workloads`` key: every
    cell does)."""
    return cell in metric.get("workloads", [cell])


def cell(name: str, bench: dict, root: str = ROOT) -> Cell:
    by_name = {w["name"]: w for w in bench["workloads"]}
    if name not in by_name:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json; known: "
                       f"{sorted(by_name)}")
    w = by_name[name]
    confs = {c["name"]: c for c in bench["configs"]}
    conf = confs[w["config"]]
    e2e = [m for m in bench["end_to_end"] if reports(m, name)]
    e2e_names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if reports(m, name) and m["moves"] in e2e_names]
    return Cell(
        name=name, chips=int(w["chips"]), config_name=conf["name"],
        config=load_json(os.path.join(root, conf["file"])),
        traffic_name=w["traffic"],
        traffic=load_json(os.path.join(BENCH_DIR, "traffic",
                                       f"{w['traffic']}.json")),
        end_to_end=e2e, per_layer=layer)


def reader(metric: str) -> ModuleType:
    return load_module(os.path.join(BENCH_DIR, "layer_metrics",
                                    f"{metric}.py"),
                       "bench_metric_" + metric.replace(".", "_"))

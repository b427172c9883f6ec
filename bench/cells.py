"""What ``BENCHMARK.json`` says about one cell, found by name.

A cell names a configuration and a traffic mix.  The configuration's
file is the one its entry names; the mix is ``bench/traffic/<name>.json``;
the correctness limits are ``bench/limits/<cell>.json``.  Metrics apply
to a cell when their ``workloads`` list names it, or when they have no
such list.
"""
from __future__ import annotations

import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "bench")


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: str = ROOT) -> dict:
    return _load(os.path.join(root, "BENCHMARK.json"))


def applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


class Cell:
    def __init__(self, name: str, root: str = ROOT):
        bench = benchmark(root)
        cells = {w["name"]: w for w in bench["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r} in BENCHMARK.json; "
                           f"known: {sorted(cells)}")
        self.name = name
        self.entry = cells[name]
        self.chips = int(self.entry["chips"])
        configs = {c["name"]: c for c in bench["configs"]}
        self.config = _load(os.path.join(
            root, configs[self.entry["config"]]["file"]))
        self.traffic = _load(os.path.join(
            root, "bench", "traffic", self.entry["traffic"] + ".json"))
        self.limits = _load(os.path.join(root, "bench", "limits",
                                         name + ".json"))["limits"]
        self.end_to_end = [m for m in bench["end_to_end"]
                           if applies(m, name)]
        self.per_layer = [m for m in bench["per_layer"] if applies(m, name)]

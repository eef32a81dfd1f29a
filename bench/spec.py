"""Find a cell's files by the names ``BENCHMARK.json`` gives them."""
from __future__ import annotations

import dataclasses
import importlib
import json
import os
import re
from typing import List

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def _name(name: str) -> str:
    if not NAME.match(name):
        raise ValueError(f"not a benchmark name: {name!r}")
    return name


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark() -> dict:
    return load_json(os.path.join(ROOT, "BENCHMARK.json"))


def kind(package: str, name: str):
    """The module ``bench.<package>.<name>`` (a builder, generator or
    reader kind)."""
    return importlib.import_module(f"bench.{package}.{_name(name)}")


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict          # the configuration file
    traffic_name: str
    traffic: dict         # the traffic mix file
    end_to_end: List[dict]
    per_layer: List[dict]

    def metrics(self, trace: bool) -> List[dict]:
        return self.per_layer if trace else self.end_to_end


def _reports(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def cell(name: str) -> Cell:
    bm = benchmark()
    w = next((w for w in bm["workloads"] if w["name"] == _name(name)), None)
    if w is None:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json")
    c = next(c for c in bm["configs"] if c["name"] == w["config"])
    cfg_path = os.path.normpath(os.path.join(ROOT, c["file"]))
    if not cfg_path.startswith(BENCH + os.sep):
        raise ValueError(f"configuration file outside bench/: {c['file']}")
    return Cell(
        name=w["name"], chips=int(w["chips"]), config_name=c["name"],
        config=load_json(cfg_path), traffic_name=w["traffic"],
        traffic=load_json(os.path.join(BENCH, "traffic",
                                       _name(w["traffic"]) + ".json")),
        end_to_end=[m for m in bm["end_to_end"] if _reports(m, w["name"])],
        per_layer=[m for m in bm["per_layer"] if _reports(m, w["name"])])


def metric_file(name: str) -> dict:
    return load_json(os.path.join(BENCH, "metrics", _name(name) + ".json"))

"""The benchmark as data: ``BENCHMARK.json`` and the files it names.

Every configuration, traffic mix, limit set and metric reader is a file of
its own, found by the name that ``BENCHMARK.json`` gives it:

- ``bench/configs/<config>.json``: the model's sizes as run, its source and
  cuts, and the family of its plain reference under ``reference``;
- ``bench/reference/<family>.py``: everything that depends on the model
  family: the plain reference, the FLOP count and the CPU test size
  (``bench/reference/__init__.py`` lists them);
- ``bench/workloads/<traffic>.json``: batch, sequence length, data
  parallelism, grad-sync mode and optimizer of the training job;
- ``bench/limits/<cell>.json``: the limit of each number that decides
  ``correct``, with the readings it was set from;
- ``bench/metrics/<metric>.py``: the reader of each metric, ``read(run)``.

A later change adds a cell, a configuration or a metric by adding such
files and entries; a configuration of a new model family brings one new
file under ``bench/reference/`` besides.
"""
from __future__ import annotations

import glob
import importlib
import json
import os
from dataclasses import dataclass, field
from typing import Dict, List

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def load_json(path: str):
    with open(path) as f:
        return json.load(f)


def find(kind: str, name: str, base: str = BENCH) -> str:
    """The one file ``<base>/<kind>/<name>.*``."""
    hits = [p for p in glob.glob(os.path.join(base, kind, name + ".*"))
            if os.path.splitext(p)[0] == os.path.join(base, kind, name)]
    if len(hits) != 1:
        raise FileNotFoundError(
            f"want one file for {kind}/{name} under {base}, found {hits}")
    return hits[0]


def reader(metric: str):
    """The module that reads ``metric``: ``bench/metrics/<metric>.py``."""
    find("metrics", metric)
    return importlib.import_module(f"bench.metrics.{metric}")


def peaks() -> Dict[str, Dict]:
    return load_json(os.path.join(BENCH, "peaks.json"))


def benchmark(root: str = ROOT) -> Dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def cell_names(chips: int) -> List[str]:
    """The cells of ``BENCHMARK.json`` that ask for ``chips`` chips."""
    return [w["name"] for w in benchmark()["workloads"]
            if w["chips"] == chips]


def applies(metric: Dict, cell: str, e2e: List[Dict]) -> bool:
    """Whether ``metric`` is reported in ``cell``: where it lists its cells,
    in those; a per-layer metric without the list, wherever the metric it
    moves is reported."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    if "moves" in metric:
        moved = next(m for m in e2e if m["name"] == metric["moves"])
        return applies(moved, cell, e2e)
    return True


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    workload: Dict
    limits: Dict
    end_to_end: List[Dict] = field(default_factory=list)
    per_layer: List[Dict] = field(default_factory=list)

    @property
    def model(self) -> Dict:
        return self.config["model"]


def cell(name: str, bench: Dict = None, base: str = BENCH) -> Cell:
    """The cell ``name`` of ``BENCHMARK.json`` with every file it names."""
    bench = bench or benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    e2e = bench["end_to_end"]
    return Cell(
        name=name, chips=entry["chips"],
        config=load_json(find("configs", entry["config"], base)),
        workload=load_json(find("workloads", entry["traffic"], base)),
        limits=load_json(find("limits", name, base)),
        end_to_end=[m for m in e2e if applies(m, name, e2e)],
        per_layer=[m for m in bench["per_layer"] if applies(m, name, e2e)])

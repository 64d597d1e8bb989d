"""A cell of ``BENCHMARK.json``, resolved to its files by name.

``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json``,
``bench/kinds/<kind>.py`` (the traffic file's ``kind``) and
``bench/metrics/<metric>.py`` are found from the names in the cell and
its metrics, so a new cell, mix, kind of traffic or metric is a new
file and a new entry, never an edit of an existing file.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
from typing import Callable, Dict, List

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: List[dict]
    per_layer: List[dict]
    bench_dir: str = BENCH_DIR

    def _module(self, sub: str, name: str):
        path = os.path.join(self.bench_dir, sub, name + ".py")
        spec = importlib.util.spec_from_file_location(
            f"bench_{sub}_" + name.replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    def reader(self, metric: str) -> Callable[[dict], object]:
        """``read(ctx)`` of ``bench/metrics/<metric>.py``."""
        return self._module("metrics", metric).read

    def kind(self):
        """``bench/kinds/<kind>.py``: its ``window(cell, seed, seconds,
        engine, info, tmp)`` makes the data, warms up and returns the
        function that drives the measured window."""
        return self._module("kinds", self.traffic["kind"])


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, benchmark: str = None,
              bench_dir: str = BENCH_DIR) -> Cell:
    """Resolve workload ``name`` of ``BENCHMARK.json``."""
    benchmark = benchmark or os.path.join(os.path.dirname(bench_dir),
                                          "BENCHMARK.json")
    with open(benchmark) as f:
        bench = json.load(f)
    cells: Dict[str, dict] = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in {benchmark}")
    w = cells[name]
    return make_cell(name, w["config"], w["traffic"], int(w["chips"]),
                     bench, bench_dir)


def make_cell(name: str, config: str, traffic: str, chips: int,
              bench: dict, bench_dir: str = BENCH_DIR) -> Cell:
    """A cell of configuration file ``config`` under traffic file
    ``traffic``, with the metrics of ``bench`` that apply to ``name``."""
    with open(os.path.join(bench_dir, "configs", config + ".json")) as f:
        cfg = json.load(f)
    with open(os.path.join(bench_dir, "traffic", traffic + ".json")) as f:
        tr = json.load(f)
    # a service runs with its own step bound; the reference follows it
    cfg["max_steps"] = int(tr.get("max_steps", cfg["max_steps"]))
    return Cell(name=name, chips=chips, config=cfg, traffic=tr,
                end_to_end=[m for m in bench["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in bench["per_layer"]
                           if _applies(m, name)],
                bench_dir=bench_dir)

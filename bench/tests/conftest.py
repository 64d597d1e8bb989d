"""Shared fixtures of the benchmark's CPU tests."""
import os
import sys
import time

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

SEED = 2 ** 33 + 17            # larger than 32 signed bits, as on the chip

# per configuration: two short kernels of it
_TINY = {
    "mibench_t2": ("repro.apps.mibench:susan_thresh",
                   "repro.apps.mibench:sha_mix"),
    "conv_t2": ("repro.apps.conv:conv_op", "repro.apps.conv:im2col_op"),
}


# the cells the tests drive: (configuration, traffic, chips)
CELLS = {
    "mibench_t2.sweep": ("mibench_t2", "sweep", 1),
    "conv_t2.sweep": ("conv_t2", "sweep", 1),
    "mibench_t2.served": ("mibench_t2", "served", 1),
}


def bench_cell(name: str):
    """Cell ``name`` of ``CELLS``, with the metrics BENCHMARK.json has."""
    import json

    from benchlib.cell import ROOT, make_cell
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    return make_cell(name, *CELLS[name], bench)


def shrink(cell):
    """``cell`` cut to a size a CPU test can run: two of its kernels,
    four bank counts, one memory latency, the XLA engine."""
    cfg = cell.config
    keep = _TINY[cfg["name"]]
    cfg["kernels"] = [k for k in cfg["kernels"] if k["builder"] in keep]
    cfg["n_banks"] = [2, 16, 64, 256]
    cfg["t_mem"] = cfg["t_mem"][:1]
    cell.traffic.update(backend="xla", campaigns_premade=2,
                        requests_premade=6, check_sample=4)
    return cell


@pytest.fixture
def tiny(tmp_path, monkeypatch):
    """``tiny(name)``: the cell ``name`` of ``CELLS`` cut by
    ``shrink``, and ``tiny.run(cell, engine=None)``: a whole run after
    the chip check, with an empty autotune cache as every run gets."""
    from benchlib.drive import run_cell
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))
    monkeypatch.delenv("REPRO_AUTOTUNE", raising=False)

    def make(name: str):
        return shrink(bench_cell(name))

    def run(cell, engine=None, seconds: float = 1.0):
        return run_cell(cell, SEED, seconds, False, time.perf_counter(),
                        engine=engine)

    make.run = run
    return make

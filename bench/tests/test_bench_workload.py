"""Configurations and traffic build the declared sizes, from the seed."""
import json
import os

import numpy as np
import pytest

from benchlib import workload
from benchlib.cell import BENCH_DIR
from conftest import bench_cell

SEED = 2 ** 33 + 17
CONFIGS = sorted(f[:-5] for f in os.listdir(os.path.join(BENCH_DIR,
                                                           "configs")))


def _cfg(name):
    with open(os.path.join(BENCH_DIR, "configs", name + ".json")) as f:
        return json.load(f)


def _same_job(a, b):
    assert len(a.programs) == len(b.programs)
    for p, q in zip(a.programs, b.programs):
        for f in workload.TABLE_FIELDS:
            assert np.array_equal(getattr(p, f), getattr(q, f))
    assert np.array_equal(a.images, b.images)
    assert a.hws == b.hws


@pytest.mark.parametrize("name", CONFIGS)
def test_campaign_has_declared_size(name):
    cfg = _cfg(name)
    hws = workload.hw_grid(cfg)
    job = workload.campaign(cfg, SEED, 0, hws)
    s = cfg["sizes"]
    assert (len(job.programs), len(hws), job.images.shape[0]) == \
        (s["G"], s["H"], s["D"])
    assert job.points == s["B"]
    assert job.images.shape[1] == cfg["mem_size"]


@pytest.mark.parametrize("name", CONFIGS)
def test_campaign_is_a_function_of_the_seed(name):
    cfg = _cfg(name)
    hws = workload.hw_grid(cfg)
    _same_job(workload.campaign(cfg, SEED, 3, hws),
              workload.campaign(cfg, SEED, 3, hws))
    other = workload.campaign(cfg, SEED + 1, 3, hws)
    assert other.points == cfg["sizes"]["B"]
    assert not np.array_equal(other.images,
                              workload.campaign(cfg, SEED, 3, hws).images)
    assert not np.array_equal(workload.campaign(cfg, SEED, 4, hws).images,
                              workload.campaign(cfg, SEED, 3, hws).images)


def test_request_has_declared_size():
    cfg = _cfg("mibench_t2")
    for k in range(len(cfg["kernels"])):
        for topo in cfg["topologies"]:
            job = workload.request(cfg, SEED, 9, k, topo)
            assert job.points == cfg["sizes"]["request_lanes"] == 48
            assert {h["bus"] for h in job.hws} == {
                cfg["topologies"][topo]["bus"]}


def test_requests_offer_the_same_work_on_every_seed():
    cell = bench_cell("mibench_t2.served")
    cfg, tr = cell.config, cell.traffic
    a = workload.request_list(cfg, tr, SEED)
    b = workload.request_list(cfg, tr, SEED)
    c = workload.request_list(cfg, tr, SEED + 1)
    assert len(a) == len(c) == tr["requests_premade"]
    for x, y in zip(a, b):
        _same_job(x, y)

    def mix(jobs):
        kern = sorted(j.programs[0].name for j in jobs)
        topo = sorted((j.hws[0]["bus"], j.hws[0]["interleaved"],
                       j.hws[0]["dma_per_pe"], j.hws[0]["smul_power_scale"])
                      for j in jobs)
        return kern, topo
    assert mix(a) == mix(c)
    assert [j.programs[0].name for j in a] != [j.programs[0].name for j in c]


def test_zipf_mix_counts():
    counts = workload._fixed_counts([1, 1 / 2, 1 / 3, 1 / 4, 1 / 5], 60)
    assert counts.sum() == 60
    assert list(counts) == sorted(counts, reverse=True)


def test_hw_grid_is_topology_major():
    cfg = _cfg("mibench_t2")
    hws = workload.hw_grid(cfg)
    assert len(hws) == 5 * 8 * 2 * 3
    assert [h["t_mem"] for h in hws[:3]] == [1, 2, 4]
    assert hws[0]["n_banks"] == 2 and hws[-1]["n_banks"] == 256
    assert hws[0]["bus"] == 0 and hws[-1]["dma_per_pe"] == 1

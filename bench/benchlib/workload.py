"""Workloads from data: a configuration file, a traffic file and a seed.

A configuration (``bench/configs/<name>.json``) fixes the campaign grid:
the kernels (built by ``repro.apps`` functions named in the file, their
data drawn from the seed), the Table-2 topologies with the hardware axes
crossed over them, the memory images, the step bound and the
characterization numbers.  A traffic file (``bench/traffic/<name>.json``)
fixes how the grid is offered: whole campaigns back to back, or
requests of one kernel under one topology, one after another.

Everything here is host-side numpy; the program is asked only for the
kernels' instruction tables and memory images, and for the types its
entry points take (``HwConfig``, ``Profile``, reduction specs).
"""
from __future__ import annotations

import dataclasses
import hashlib
import importlib
from typing import Dict, List, Sequence

import numpy as np

TABLE_FIELDS = ("ops", "dest", "srcA", "srcB", "imm")
HW_FIELDS = ("smul_lat", "smul_power_scale", "bus", "interleaved",
             "n_banks", "dma_per_pe", "t_mem", "t_clk_ns")


def data_seed(seed: int, *path: int) -> int:
    """A 32-bit seed for one item, drawn from the run seed and a path of
    indices (campaign or request, kernel, data copy)."""
    return int(np.random.SeedSequence([int(seed), *map(int, path)])
               .generate_state(1)[0])


def hw_grid(cfg: dict, topologies: Sequence[str] = None) -> List[dict]:
    """Topology x n_banks x smul_lat x t_mem, topology-major."""
    out = []
    for name in topologies or cfg["topologies"]:
        base = cfg["topologies"][name]
        for nb in cfg["n_banks"]:
            for sl in cfg["smul_lat"]:
                for tm in cfg["t_mem"]:
                    out.append(dict(base, n_banks=nb, smul_lat=sl, t_mem=tm))
    return out


def hw_arrays(hws: Sequence[dict]) -> Dict[str, np.ndarray]:
    return {f: np.asarray([h[f] for h in hws]) for f in HW_FIELDS}


def _kernel(spec: dict, seed: int):
    mod, _, fn = spec["builder"].partition(":")
    return getattr(importlib.import_module(mod), fn)(
        **spec.get("args", {}), seed=seed)


def build_kernels(cfg: dict, seed: int, *path: int):
    """Each kernel of the configuration, under ``data_copies`` data seeds.

    Returns ``(programs, images)``: one program per kernel (its first
    copy) and the distinct memory images of all copies, in order."""
    programs, images = [], []
    for k, spec in enumerate(cfg["kernels"]):
        kd = 0 if cfg.get("shared_data") else k
        for j in range(cfg.get("data_copies", 1)):
            case = _kernel(spec, data_seed(seed, *path, kd, j))
            if j == 0:
                programs.append(case.program)
            img = np.asarray(case.mem_init, np.int32)
            if not any(np.array_equal(img, x) for x in images):
                images.append(img)
    return programs, np.stack(images)


def tables_of(program) -> Dict[str, np.ndarray]:
    return {f: np.asarray(getattr(program, f)) for f in TABLE_FIELDS}


def fingerprint(programs, images) -> str:
    h = hashlib.sha256()
    for p in programs:
        for f in TABLE_FIELDS:
            h.update(np.ascontiguousarray(getattr(p, f), np.int32).tobytes())
    h.update(np.ascontiguousarray(images, np.int32).tobytes())
    return h.hexdigest()[:16]


def make_profile(cfg: dict):
    """The configuration's characterization as the program's ``Profile``."""
    from repro.core.characterization import Profile
    p = cfg["profile"]
    return Profile(p_flat=float(p["p_flat"]),
                   lat=np.asarray(p["lat"], np.int32), t_mem=int(p["t_mem"]),
                   p_dec=np.asarray(p["p_dec"], np.float32),
                   p_act=np.asarray(p["p_act"], np.float32),
                   p_idle=float(p["p_idle"]),
                   e_src=np.asarray(p["e_src"], np.float32),
                   e_sw_op=float(p["e_sw_op"]), e_sw_mux=float(p["e_sw_mux"]),
                   mulzero=float(p["mulzero"]), t_clk_ns=float(p["t_clk_ns"]))


def make_hw(hws: Sequence[dict]):
    from repro.core.hwconfig import HwConfig
    return [HwConfig(**h) for h in hws]


def make_reduce(spec: dict):
    from repro.analysis.pareto import ParetoFront
    if spec["kind"] != "pareto":
        raise ValueError(f"unknown reduction {spec['kind']!r}")
    return ParetoFront(tuple(spec["axes"]), int(spec["max_points"]))


@dataclasses.dataclass
class Job:
    """One unit of offered work: a whole campaign or one request."""
    index: int
    programs: list
    images: np.ndarray
    hws: List[dict]

    @property
    def points(self) -> int:
        return len(self.programs) * len(self.hws) * int(self.images.shape[0])


def campaign(cfg: dict, seed: int, index: int, hws: List[dict]) -> Job:
    programs, images = build_kernels(cfg, seed, index)
    return Job(index, programs, images, hws)


def _fixed_counts(weights: Sequence[float], n: int) -> np.ndarray:
    """Integer counts summing to n, proportional to weights (largest
    remainder), so every seed gets the same mix."""
    w = np.asarray(weights, np.float64)
    raw = w / w.sum() * n
    counts = np.floor(raw).astype(int)
    for i in np.argsort(-(raw - counts), kind="stable")[:n - counts.sum()]:
        counts[i] += 1
    return counts


def request_list(cfg: dict, traffic: dict, seed: int) -> List[Job]:
    """``requests_premade`` requests, sent in this order.

    How many requests go to each kernel (Zipf over the listed order, the
    traffic file's ``kernel_zipf_s``) and to each topology (uniform) is
    fixed by the traffic file; the seed only orders them and draws each
    request's data, so every seed offers the same work."""
    n = int(traffic["requests_premade"])
    kernels, topo_names = cfg["kernels"], list(cfg["topologies"])
    s = float(traffic["kernel_zipf_s"])
    k_of = np.repeat(np.arange(len(kernels)), _fixed_counts(
        [1.0 / (r + 1) ** s for r in range(len(kernels))], n))
    t_of = np.repeat(np.arange(len(topo_names)), _fixed_counts(
        [1.0] * len(topo_names), n))
    order = np.random.default_rng(data_seed(seed, 1 << 30))
    k_of = order.permutation(k_of)
    t_of = order.permutation(t_of)
    return [request(cfg, seed, i, int(k_of[i]), topo_names[int(t_of[i])])
            for i in range(n)]


def request(cfg: dict, seed: int, index: int, kernel: int,
            topology: str) -> Job:
    case = _kernel(cfg["kernels"][kernel], data_seed(seed, index, kernel, 0))
    return Job(index, [case.program],
               np.asarray(case.mem_init, np.int32)[None],
               hw_grid(cfg, [topology]))

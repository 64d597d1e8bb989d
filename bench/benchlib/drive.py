"""One run of one cell: set-up, the measured window, the check.

``run_cell`` is the whole run behind ``bench/run.py``.  The traffic
file's ``kind`` names the module ``bench/kinds/<kind>.py`` that makes
the data, warms up and drives the window (``Cell.kind``), so a new
kind of traffic is a new file.

A campaign or request that starts inside the window runs to its end.
The benchmark's own host spans (``jax.profiler.TraceAnnotation``) mark
warm-up, each campaign or request, and the window, so that a traced run
can say what the host was doing while the device idled.
"""
from __future__ import annotations

import contextlib
import gc
import json
import os
import shutil
import statistics
import sys
import tempfile
import time
from typing import Callable, Dict, List, Optional

import numpy as np

from . import compare, reference, trace_reduce, workload
from .cell import Cell

WARM = 1 << 20                      # index space of warm-up data
# XLA compiles (persistent-cache loads included) seen by this process
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
COMPILES = {"n": 0, "s": 0.0}
# the interpreter's garbage collections seen by this process, and their time
GC = {"n": 0, "s": 0.0, "t0": 0.0}


def _count_compiles_and_gc():
    import jax
    if COMPILES.get("listening"):
        return

    def on_duration(event, secs, **_):
        if event == COMPILE_EVENT:
            COMPILES["n"] += 1
            COMPILES["s"] += secs
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    COMPILES["listening"] = True

    def on_gc(phase, _info):
        if phase == "start":
            GC["t0"] = time.perf_counter()
        else:
            GC["n"] += 1
            GC["s"] += time.perf_counter() - GC["t0"]
    gc.callbacks.append(on_gc)


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


# -- reference --------------------------------------------------------------

def reference_lanes(cfg: dict, job: workload.Job,
                    energy_dtype: str = "float64") -> List[Dict]:
    """Reference fields of every lane, per kernel, in the canonical
    lane order ``h * D + d``."""
    hw = workload.hw_arrays(job.hws)
    H, D = len(job.hws), int(job.images.shape[0])
    out = []
    for prog in job.programs:
        tables = workload.tables_of(prog)
        f = {k: np.zeros(H * D, np.float64) for k in
             ("latency_cc", "energy_pj", "power_mw", "checksum",
              "steps_executed")}
        for d in range(D):
            ex = reference.execute(tables, job.images[d], cfg["max_steps"])
            ev = reference.evaluate(tables, ex, hw, cfg["profile"],
                                    cfg["mem_size"], energy_dtype)
            sl = slice(d, H * D, D)
            f["latency_cc"][sl] = ev["latency_cc"]
            f["energy_pj"][sl] = ev["energy_pj"]
            f["power_mw"][sl] = ev["power_mw"]
            f["checksum"][sl] = ex.checksum
            f["steps_executed"][sl] = ex.steps
        out.append(f)
    return out


def reference_answer(lanes: List[Dict], max_points: int) -> Dict:
    """The Pareto answer that reference lanes give, in the program's
    ``ReducedResult`` layout (flat lane indices, ``-1`` padding)."""
    G, n = len(lanes), len(lanes[0]["latency_cc"])
    ans = {f: np.zeros((G, max_points)) for f in
           ("latency_cc", "energy_pj", "power_mw", "checksum",
            "steps_executed")}
    ans["indices"] = np.full((G, max_points), -1, np.int64)
    ans["clipped"] = np.zeros(G, np.int64)
    for g, f in enumerate(lanes):
        pos = reference.pareto_front(f["latency_cc"], f["energy_pj"])
        ans["clipped"][g] = max(0, pos.size - max_points)
        pos = pos[:max_points]
        ans["indices"][g, :pos.size] = g * n + pos
        for k in ("latency_cc", "energy_pj", "power_mw", "checksum",
                  "steps_executed"):
            ans[k][g, :pos.size] = f[k][pos]
    return ans


def judge(cfg: dict, job: workload.Job, answer: Dict,
          energy_limit: float, lanes=None) -> Dict[str, float]:
    lanes = lanes if lanes is not None else reference_lanes(cfg, job)
    readings = []
    for g, f in enumerate(lanes):
        row = {k: np.asarray(answer[k])[g] for k in
               ("indices", "latency_cc", "energy_pj", "power_mw",
                "checksum", "steps_executed")}
        row["clipped"] = np.asarray(answer["clipped"])[g]
        readings.append(compare.judge_front(
            row, f, g * len(f["latency_cc"]), energy_limit))
    return compare.worst(readings)


def answer_of(res) -> Dict:
    """A ``ReducedResult`` (or a folded client answer) as numpy arrays."""
    if isinstance(res, dict):
        return {k: np.asarray(v) for k, v in res.items()}
    return {k: np.asarray(getattr(res, k)) for k in res._fields}


# -- engines ----------------------------------------------------------------

def program_engine(cell: Cell):
    """``engine(job) -> answer`` through ``dse.sweep``, as the traffic
    file sets it up."""
    import jax
    from repro.core import dse
    cfg, tr = cell.config, cell.traffic
    profile = workload.make_profile(cfg)
    spec = workload.make_reduce(tr["reduce"])
    hw_cache = {}

    def engine(job: workload.Job):
        key = id(job.hws)
        if key not in hw_cache:
            hw_cache[key] = workload.make_hw(job.hws)
        res = dse.sweep(programs=job.programs, profile=profile,
                        hw_configs=hw_cache[key], mem_images=job.images,
                        max_steps=cfg["max_steps"], mem_size=cfg["mem_size"],
                        backend=tr["backend"], reduce=spec)
        return answer_of(jax.block_until_ready(res))
    return engine


def control_engine(cell: Cell):
    """The precision control: the reference in the program's place, its
    energy summed in bfloat16 (the configuration states float32)."""
    cfg, tr = cell.config, cell.traffic

    def engine(job: workload.Job):
        lanes = reference_lanes(cfg, job, "bfloat16")
        return reference_answer(lanes, int(tr["reduce"]["max_points"]))
    return engine


# -- the window -------------------------------------------------------------

class Window:
    """What the measured window produced."""

    def __init__(self):
        self.done: List[tuple] = []      # (job, answer, t_start, t_end)
        self.failed = 0
        self.lost = 0                    # no answer, or an incomplete one
        self.attempted = 0
        self.records: List[int] = []
        self.latency_s: List[float] = []
        self.errors: List[str] = []

    def fail(self, why: str, lost: bool = True):
        self.failed += 1
        self.lost += lost
        self.errors.append(why[:300])

    def lose(self, why: str):
        self.fail(why, lost=True)


def trip_count(ans: Dict, g: int) -> int:
    """The largest ``steps_executed`` on kernel ``g``'s front."""
    return int(np.max(ans["steps_executed"][g][ans["indices"][g] >= 0],
                      initial=0))


# -- the run ----------------------------------------------------------------

def _device_info(n: int) -> dict:
    import jax
    devs = jax.devices()
    peak = 0
    for d in devs[:n]:
        try:
            peak = max(peak, int(d.memory_stats()["peak_bytes_in_use"]))
        except (TypeError, KeyError, RuntimeError):
            pass
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": peak}


def _quantile(xs, q):
    xs = sorted(xs)
    if len(xs) == 1:
        return xs[0]
    return statistics.quantiles(xs, n=100, method="inclusive")[
        int(round(q * 100)) - 1]


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             t_setup0: float, engine: Optional[Callable] = None) -> dict:
    """The whole run after the chip check; returns the result object."""
    from repro.core import dse
    tr = cell.traffic
    _count_compiles_and_gc()
    tmp = tempfile.mkdtemp(prefix="bench-")
    info: Dict = {}
    try:
        window = cell.kind().window(cell, seed, seconds, engine, info,
                                      tmp)
        win = Window()
        counts0 = dict(dse.TRACE_COUNTS)
        compiles0 = dict(COMPILES)
        gc0 = dict(GC)
        trace_dir = os.path.join(tmp, "trace")
        t_win0 = time.perf_counter()
        setup_s = t_win0 - t_setup0
        with (trace_reduce.tracing(trace_dir) if trace
              else contextlib.nullcontext()):
            with span("window"):
                window(win)
            t_win1 = time.perf_counter()
        info.pop("close", lambda: None)()
        info["trace_counts_delta"] = {
            k: dse.TRACE_COUNTS[k] - counts0[k] for k in counts0}
        info["compiles_in_window"] = COMPILES["n"] - compiles0["n"]
        info["compile_s_in_window"] = COMPILES["s"] - compiles0["s"]
        info["gc_in_window"] = GC["n"] - gc0["n"]
        info["gc_s_in_window"] = GC["s"] - gc0["s"]
        device = _device_info(cell.chips)
        summary = (trace_reduce.summarize(trace_dir, cell.chips) if trace
                   else None)
        metrics = _metrics(cell, win, setup_s, summary, info, trace)
        checks = _check(cell, seed, win, tr)
        info["window_s"] = t_win1 - t_win0
        info["errors"] = win.errors[:5]
        print(json.dumps({"workload": cell.name, "seed": seed, **info}),
              flush=True)
        correct = bool(win.done) and all(v["value"] <= v["limit"]
                                         for v in checks.values())
        for k, v in checks.items():
            log(f"check {k} {v['value']!r} limit {v['limit']!r}")
        out = {"correct": correct, "attempted": win.attempted,
               "failed": win.failed, "metrics": metrics, "device": device}
        if summary is not None:
            device["busy_s"] = summary["busy_s"]
            device["window_s"] = summary["window_s"]
            out["breakdown"] = {"device_ops": summary["device_ops"],
                                "idle_gaps": summary["idle_gaps"]}
        out["checks"] = checks
        return out
    finally:
        info.pop("close", lambda: None)()
        shutil.rmtree(tmp, ignore_errors=True)


def _metrics(cell: Cell, win: Window, setup_s: float, summary, info,
             trace: bool) -> Dict:
    out = {}
    if not trace:
        for m in cell.end_to_end:
            v = _e2e(m["name"], win, setup_s)
            if v is not None:
                out[m["name"]] = {"value": v, "unit": m["unit"]}
        return out
    ctx = {"kind": cell.traffic["kind"], "trace": summary,
           "jobs": len(win.done), "records": win.records,
           "trace_counts_delta": info["trace_counts_delta"],
           "compiles": info["compiles_in_window"]}
    missing = []
    for m in cell.per_layer:
        v = cell.reader(m["name"])(ctx)
        if v is None and "workloads" in m:
            missing.append(m["name"])
        elif v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    # a metric that names this cell among its workloads has something to
    # read here: finding nothing is a broken reading, not a result
    if missing:
        raise RuntimeError(f"{cell.name}: per-layer metrics {missing} "
                           "found nothing to read in the traced window")
    return out


def _e2e(name: str, win: Window, setup_s: float) -> Optional[float]:
    if name == "setup_s":
        return setup_s
    if not win.done:
        return None
    if name == "points_per_s":
        t0 = min(d[2] for d in win.done)
        t1 = max(d[3] for d in win.done)
        return sum(d[0].points for d in win.done) / (t1 - t0)
    if name == "request_p50_s":
        return _quantile(win.latency_s, 0.5)
    if name == "request_p90_s":
        return _quantile(win.latency_s, 0.9)
    raise ValueError(f"no end-to-end metric named {name!r}")


def _check(cell: Cell, seed: int, win: Window, tr: dict) -> Dict:
    """Judge a sample of the window's answers, drawn from the seed, with
    the longest job always in it."""
    lim = cell.config["limits"]
    n = min(len(win.done), int(tr["check_sample"]))
    readings = {"wrong_lanes": 0, "front_errors": 0, "energy_rel_gap": 0.0}
    if n:
        rng = np.random.default_rng(workload.data_seed(seed, 1 << 29))
        steps = [max(int(np.max(a["steps_executed"], initial=0)), 0)
                 for _, a, _, _ in win.done]
        pick = {int(np.argmax(steps))}
        for i in rng.permutation(len(win.done)):
            if len(pick) >= n:
                break
            pick.add(int(i))
        readings = compare.worst(
            judge(cell.config, win.done[i][0], win.done[i][1],
                  lim["energy_rel_gap"]) for i in sorted(pick))
    checks = {"answers_lost": {"value": win.lost, "limit": 0}}
    checks.update({k: {"value": readings[k], "limit": lim[k]} for k in
                   ("wrong_lanes", "front_errors", "energy_rel_gap")})
    return checks

"""Traffic kind ``served``: one closed-loop client of the HTTP service.

A ``SweepTransport(SweepService(...))`` runs in-process on loopback,
with checkpoints under the run's temporary directory and every knob the
traffic file does not name at the program's default.  One
``SweepClient`` sends one request, waits for its folded front, and sends
the next: a DSE script that waits for each answer.  Each request is one
kernel x the variants of one topology x that kernel's own image; the
mix (``workload.request_list``) is the same on every seed, in another
order.  A request's latency is submit to folded front.  A request that
starts inside the window runs to its end.
"""
from __future__ import annotations

import os
import time

import numpy as np

from benchlib import drive, workload


def window(cell, seed: int, seconds: float, engine, info: dict, tmp: str):
    """``engine``, when given, answers each request in place of the
    service (the precision control, a planted fault)."""
    cfg, tr = cell.config, cell.traffic
    spec = workload.make_reduce(tr["reduce"])
    if engine is None:
        from repro.service import SweepClient, SweepService, SweepTransport
        svc = SweepService(workload.make_profile(cfg),
                           max_steps=cfg["max_steps"],
                           mem_size=cfg["mem_size"], backend=tr["backend"],
                           ckpt_root=os.path.join(tmp, "ckpt"))
        transport = SweepTransport(svc)
        host, port = transport.start()
        info["close"] = transport.close
        client = SweepClient(host, port, seed=workload.data_seed(seed, 0))

    def send(job: workload.Job):
        """``(answer, records folded, incomplete, what went wrong)``: an
        incomplete answer is a lost one; a unit degraded off the compiled
        engine is a failure with a whole answer."""
        if engine is not None:
            return engine(job), 0, False, ""
        res = client.sweep(job.programs, workload.make_hw(job.hws),
                           job.images, reduce=spec)
        ans = drive.answer_of(res.arrays)
        clipped = int(np.asarray(ans["clipped"]).sum())
        incomplete = bool(res.expired or res.skipped_lanes or clipped)
        bad = ""
        if incomplete or res.degraded_units:
            bad = (f"request {job.index}: expired={res.expired} "
                   f"skipped={res.skipped_lanes} clipped={clipped} "
                   f"degraded={res.degraded_units}")
        return ans, res.stats.records_folded, incomplete, bad

    topo0 = next(iter(cfg["topologies"]))
    warm = [workload.request(cfg, seed, drive.WARM + k, k, topo0)
            for k in range(len(cfg["kernels"]))]
    info["fingerprint"] = workload.fingerprint(
        [j.programs[0] for j in warm],
        np.concatenate([j.images for j in warm]))
    info["trip_counts"] = {}
    with drive.span("warmup"):
        for k, job in enumerate(warm):
            ans = send(job)[0]
            name = cfg["kernels"][k]["builder"].rpartition(":")[2]
            info["trip_counts"][name] = drive.trip_count(ans, 0)
    jobs = workload.request_list(cfg, tr, seed)

    def run(win: drive.Window):
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < seconds:
            job = jobs[i % len(jobs)]
            i += 1
            win.attempted += 1
            ts = time.perf_counter()
            try:
                with drive.span("request"):
                    ans, records, incomplete, bad = send(job)
            except Exception as e:  # noqa: BLE001 - a failed request
                win.lose(repr(e))
                continue
            te = time.perf_counter()
            win.latency_s.append(te - ts)
            win.records.append(records)
            win.done.append((job, ans, ts, te))
            if bad:
                win.fail(bad, lost=incomplete)
        info["data_reused"] = max(0, i - len(jobs))
    return run

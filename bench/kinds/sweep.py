"""Traffic kind ``sweep``: a closed loop of whole campaigns.

One caller runs campaigns back to back through ``dse.sweep``.  Campaign
``i`` draws its data from ``(seed, i)``; ``campaigns_premade`` of them
are made before the window and reused in order if the window runs more.
A campaign that starts inside the window runs to its end.
"""
from __future__ import annotations

import time

import numpy as np

from benchlib import drive, workload


def window(cell, seed: int, seconds: float, engine, info: dict, tmp: str):
    cfg, tr = cell.config, cell.traffic
    engine = engine or drive.program_engine(cell)
    hws = workload.hw_grid(cfg)
    jobs = [workload.campaign(cfg, seed, i, hws)
            for i in range(int(tr["campaigns_premade"]))]
    warm = workload.campaign(cfg, seed, drive.WARM, hws)
    info["fingerprint"] = workload.fingerprint(warm.programs, warm.images)
    info["grid"] = {"G": len(warm.programs), "H": len(hws),
                    "D": int(warm.images.shape[0]), "B": warm.points}
    with drive.span("warmup"):
        ans = engine(warm)
    info["trip_counts"] = {
        spec["builder"].rpartition(":")[2]: drive.trip_count(ans, g)
        for g, spec in enumerate(cfg["kernels"])}

    def run(win: drive.Window):
        t0 = time.perf_counter()
        i = 0
        while time.perf_counter() - t0 < seconds:
            job = jobs[i % len(jobs)]
            i += 1
            win.attempted += 1
            ts = time.perf_counter()
            try:
                with drive.span("campaign"):
                    ans = engine(job)
            except Exception as e:  # noqa: BLE001 - a failed campaign
                win.lose(repr(e))
                continue
            te = time.perf_counter()
            if int(np.asarray(ans["clipped"]).sum()):
                win.lose(f"campaign {job.index}: clipped front")
            win.done.append((job, ans, ts, te))
        info["data_reused"] = max(0, i - len(jobs))
    return run

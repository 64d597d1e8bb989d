"""The comparison that decides ``correct``.

An answer is one Pareto front per kernel over (latency_cc, energy_pj):
the entries a campaign or a request returned.  It is judged against the
plain reference (``reference.py``) evaluated on every lane of that
kernel, by three numbers:

* ``wrong_lanes`` -- entries whose index is not a lane of that kernel,
  repeats an index, or whose latency_cc, checksum or steps_executed
  differ from the reference lane; and a front that reports clipped
  points.  Limit 0.
* ``front_errors`` -- entries that the reference shows to be dominated,
  plus reference front points that the answer lacks.  Energies are
  float32 sums in the program and float64 sums here, so a point whose
  membership turns on a difference within ``2 * energy limit`` is left
  undecided.  Limit 0.
* ``energy_rel_gap`` -- the largest relative gap between an entry's
  energy_pj or power_mw and the reference lane's.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

DISCRETE = ("latency_cc", "checksum", "steps_executed")


def _rel(got, want):
    want = np.asarray(want, np.float64)
    return np.abs(np.asarray(got, np.float64) - want) / np.maximum(
        np.abs(want), 1e-30)


def judge_front(front: Dict[str, np.ndarray], ref: Dict[str, np.ndarray],
                lane_offset: int, energy_limit: float) -> Dict[str, float]:
    """One kernel's answer against the reference.

    ``front`` holds the answer row of that kernel: ``indices`` (flat
    lane indices, ``-1`` for empty slots) and the result fields, plus
    ``clipped``.  ``ref`` holds the reference fields of the kernel's
    ``n`` lanes, whose flat indices are ``lane_offset .. lane_offset+n``.
    """
    idx = np.asarray(front["indices"]).reshape(-1)
    valid = idx >= 0
    n = len(ref["latency_cc"])
    pos = idx[valid].astype(np.int64) - lane_offset
    wrong = int(np.asarray(front.get("clipped", 0)).sum() > 0)
    inside = (pos >= 0) & (pos < n)
    wrong += int((~inside).sum())
    wrong += int(pos[inside].size - np.unique(pos[inside]).size)
    pos_in = pos[inside]
    gap = 0.0
    for f in DISCRETE:
        got = np.asarray(front[f]).reshape(-1)[valid][inside]
        wrong += int((got.astype(np.int64)
                      != np.asarray(ref[f], np.int64)[pos_in]).sum())
    for f in ("energy_pj", "power_mw"):
        got = np.asarray(front[f]).reshape(-1)[valid][inside]
        if got.size:
            gap = max(gap, float(_rel(got, np.asarray(ref[f])[pos_in])
                                 .max()))

    lat = np.asarray(ref["latency_cc"], np.float64)
    en = np.asarray(ref["energy_pj"], np.float64)
    tol = 2.0 * energy_limit
    le_lat = lat[:, None] <= lat[None, :]            # [p, q]: lat_p <= lat_q
    # q surely dominated: some p no slower and clearly less energy
    sure_dom = (le_lat & (en[:, None] < en[None, :] * (1 - tol))).any(axis=0)
    # q surely on the front: no other point (bar exact duplicates) is no
    # slower and within the tolerance of q's energy or below it
    dup = (lat[:, None] == lat[None, :]) & (en[:, None] == en[None, :])
    near = le_lat & (en[:, None] <= en[None, :] * (1 + tol)) & ~dup
    sure_front = ~near.any(axis=0)
    listed = np.zeros(n, bool)
    listed[pos_in] = True
    front_errors = int((listed & sure_dom).sum()
                       + (sure_front & ~listed).sum())
    return {"wrong_lanes": wrong, "front_errors": front_errors,
            "energy_rel_gap": gap}


def worst(readings) -> Dict[str, float]:
    """Fold per-kernel readings: counts add up, the gap takes the max."""
    out = {"wrong_lanes": 0, "front_errors": 0, "energy_rel_gap": 0.0}
    for r in readings:
        out["wrong_lanes"] += r["wrong_lanes"]
        out["front_errors"] += r["front_errors"]
        out["energy_rel_gap"] = max(out["energy_rel_gap"],
                                    r["energy_rel_gap"])
    return out

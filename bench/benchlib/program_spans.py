"""The program's own spans in a profiler trace of the window, and the
per-layer readings taken from them and from the program's counters.

The program marks each layer boundary of the DSE path with a span named
``repro.<layer>.<what>`` (``repro.obs.span``, a
``jax.profiler.TraceAnnotation``) and counts work done in
``repro.obs.COUNTS``.  The spans land in the same ``.xplane.pb`` as the
device's operations, on the host line (thread) that ran them and on the
same clock.  ``trace_reduce`` reads the device and the benchmark's own
spans; this module reads, from the same trace:

* ``program_spans``: every ``repro.*`` event of the host planes, with
  its line;
* ``seconds``: a span's seconds, counting only its outermost
  occurrences on each line (a recursive ``repro.dse.sweep`` once), less
  the time of a named span inside them where asked;
* ``idle_gaps``: each idle gap of the device put down to the innermost
  program span (the latest start; of equal starts, the earliest end)
  that covers the gap's midpoint, on any line, else to the benchmark's
  innermost span as ``trace_reduce`` does, else to ``no span``.

``measure`` runs one traced window of a cell's traffic and reads, with
the accepted readers of ``bench/metrics`` and those that read these
spans and counters, everything at once; ``bench/span_report.py`` runs it
on the chip.  A reader of a program span or counter takes the context
``drive`` gives its readers plus ``trace["program_spans"]`` (the spans
clipped to the window) and ``obs_counts_delta`` (``repro.obs.COUNTS``
over the window), and reads nothing where those are missing.
"""
from __future__ import annotations

import collections
import glob
import heapq
import os
import shutil
import tempfile
import time
from typing import Dict, List, Optional, Tuple

from . import drive, trace_reduce

PREFIX = "repro."
Span = Tuple[str, float, float, str]          # name, start_ns, end_ns, line

# the per-layer metrics that read program spans and counters
METRICS = ("plan_s_per_campaign", "dse_host_s_per_campaign",
           "configs_stacked_per_campaign", "queue_wait_s_per_request",
           "admit_s_per_request", "unit_host_s_per_request",
           "checkpoint_s_per_request")


def program_spans(pd) -> List[Span]:
    """The ``repro.*`` events of a ``ProfileData``'s host planes; a
    line is ``<plane>#<index>``, one per host thread."""
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for i, line in enumerate(plane.lines):
            out += [(e.name, e.start_ns, e.end_ns, f"{plane.name}#{i}")
                    for e in line.events if e.name.startswith(PREFIX)]
    return out


def clip(spans: List[Span], lo: float, hi: float) -> List[Span]:
    return [(n, max(s, lo), min(e, hi), ln) for n, s, e, ln in spans
            if e > lo and s < hi]


def _covered_ns(intervals) -> float:
    return sum(e - s for s, e in trace_reduce.union(intervals))


def seconds(spans: List[Span], name: str,
            less: Optional[str] = None) -> Optional[float]:
    """Seconds of ``name``'s outermost occurrences, summed over lines;
    with ``less``, without the part that ``less`` spans cover on the
    same line.  None where ``name`` does not occur."""
    lines = {ln for n, _, _, ln in spans if n == name}
    if not lines:
        return None
    total = 0.0
    for ln in lines:
        own = [(s, e) for n, s, e, li in spans if li == ln and n == name]
        inner = [(s, e) for n, s, e, li in spans if li == ln and n == less]
        # |own \ inner| = |own u inner| - |inner|
        total += _covered_ns(own + inner) - _covered_ns(inner)
    return total * 1e-9


def _innermost(mids: List[float], spans) -> List[Optional[str]]:
    """For ascending ``mids``, the name of the covering span with the
    latest start (of equal starts, the earliest end), or None."""
    order = sorted(spans, key=lambda sp: sp[1])
    heap, i, out = [], 0, []
    for mid in mids:
        while i < len(order) and order[i][1] <= mid:
            heapq.heappush(heap, (-order[i][1], order[i][2], i))
            i += 1
        # a span that ended never covers a later midpoint
        while heap and order[heap[0][2]][2] <= mid:
            heapq.heappop(heap)
        out.append(order[heap[0][2]][0] if heap else None)
    return out


def _window(spans) -> Tuple[float, float]:
    win = [s for s in spans if s[0] == "window"]
    if not win:
        raise ValueError("trace has no 'window' span")
    return win[0][1], win[0][2]


def idle_gaps(devices, spans, program: List[Span]) -> List[list]:
    """Idle seconds of the window by what covered each gap, program
    spans first, the mean over the devices, largest first."""
    lo, hi = _window(spans)
    inner = [s for s in spans if s[0] != "window"]
    total: Dict[str, float] = {}
    for dev in sorted(devices):
        busy = trace_reduce.union([(max(s, lo), min(e, hi))
                                   for _, s, e in devices[dev]
                                   if e > lo and s < hi])
        gaps = trace_reduce.gaps(busy, lo, hi)
        mids = [0.5 * (s + e) for s, e in gaps]
        for (s, e), prog, bench in zip(gaps, _innermost(mids, program),
                                       _innermost(mids, inner)):
            who = prog or bench or "no span"
            total[who] = total.get(who, 0.0) + (e - s) * 1e-9
    n = max(len(devices), 1)
    return sorted(([k, v / n] for k, v in total.items()),
                  key=lambda kv: -kv[1])


def reduce(pd, chips: int) -> dict:
    """``trace_reduce``'s summary of a ``ProfileData``, plus
    ``program_spans`` clipped to the window and ``idle_gaps_by_span``."""
    devices, spans, kernels = trace_reduce.events_of(pd, chips)
    out = trace_reduce.reduce_events(devices, spans, kernels)
    prog = clip(program_spans(pd), *_window(spans))
    out["program_spans"] = prog
    out["idle_gaps_by_span"] = idle_gaps(devices, spans, prog)
    return out


def summarize(trace_dir: str, chips: int) -> dict:
    """``reduce`` of the one ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return reduce(ProfileData.from_file(path), chips)


def per_job(ctx, kind: str, name: str,
            less: Optional[str] = None) -> Optional[float]:
    """``seconds(name, less)`` per job of the window, in a cell of
    traffic ``kind``; None in another kind, where the summary holds no
    program spans, or where ``name`` never ran."""
    tr = ctx["trace"]
    if (ctx["kind"] != kind or not tr or not ctx["jobs"]
            or "program_spans" not in tr):
        return None
    s = seconds(tr["program_spans"], name, less)
    return None if s is None else s / ctx["jobs"]


def measure(cell, seed: int, window_s: float, t_start: float) -> dict:
    """One traced window of ``cell``'s traffic: its end-to-end metrics
    (traced), the readings of the cell's accepted per-layer metrics and
    of ``METRICS``, the idle gaps by program span and the program spans
    counted per job.  Nothing is checked against the reference."""
    from repro import obs
    from repro.core import dse
    drive._count_compiles_and_gc()
    tmp = tempfile.mkdtemp(prefix="bench-spans-")
    info: Dict = {}
    try:
        window = cell.kind().window(cell, seed, window_s, None, info, tmp)
        win = drive.Window()
        counts0 = dict(obs.COUNTS)
        traces0 = dict(dse.TRACE_COUNTS)
        compiles0 = drive.COMPILES["n"]
        trace_dir = os.path.join(tmp, "trace")
        setup_s = time.perf_counter() - t_start
        with trace_reduce.tracing(trace_dir):
            with drive.span("window"):
                window(win)
        info.pop("close", lambda: None)()
        summary = summarize(trace_dir, cell.chips)
        ctx = {"kind": cell.traffic["kind"], "trace": summary,
               "jobs": len(win.done), "records": win.records,
               "trace_counts_delta": {k: dse.TRACE_COUNTS[k] - traces0[k]
                                      for k in traces0},
               "compiles": drive.COMPILES["n"] - compiles0,
               "obs_counts_delta": {k: obs.COUNTS[k] - counts0[k]
                                    for k in counts0}}
        names = [m["name"] for m in cell.per_layer] + list(METRICS)
        readings = {n: cell.reader(n)(ctx) for n in names}
        idle = sum(v for _, v in summary["idle_gaps_by_span"])
        in_program = sum(v for k, v in summary["idle_gaps_by_span"]
                         if k.startswith(PREFIX))
        jobs = max(len(win.done), 1)
        events = collections.Counter(
            n for n, *_ in summary["program_spans"])
        return {
            "workload": cell.name, "seed": seed, "jobs": len(win.done),
            "failed": win.failed,
            "end_to_end": {m["name"]: drive._e2e(m["name"], win, setup_s)
                           for m in cell.end_to_end},
            "per_layer": {k: v for k, v in readings.items()
                          if v is not None},
            "window_s": summary["window_s"], "busy_s": summary["busy_s"],
            "idle_share_in_program_spans": in_program / idle if idle
            else None,
            "idle_gaps_by_span": summary["idle_gaps_by_span"],
            "idle_gaps": summary["idle_gaps"],
            "device_ops": summary["device_ops"],
            "span_events_per_job": len(summary["program_spans"]) / jobs,
            "span_events_per_job_by_name": {
                k: v / jobs for k, v in sorted(events.items())},
            "obs_counts_delta": ctx["obs_counts_delta"],
            "errors": win.errors[:5],
        }
    finally:
        info.pop("close", lambda: None)()
        shutil.rmtree(tmp, ignore_errors=True)

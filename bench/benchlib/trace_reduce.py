"""From a profiler trace of the window to device busy time, the device
operations that took the time, and what the host did while it idled.

The trace is JAX's ``.xplane.pb``, read with ``jax.profiler.ProfileData``.
A device is a plane named ``/device:TPU:<n>``, and only the planes of
the ``chips`` devices a cell uses (``n < chips``) count: a host may hold
more chips than the cell asks for, and those idle.  A device's
operations are the events of its ``XLA Ops`` line.  On a TPU v5e such an
event is named by its whole HLO instruction (``%body.3 = (...)
custom-call(...), custom_call_target="tpu_custom_call", ...``); an
operation is known by the instruction's name alone (``body.3``,
``op_name``).  Busy time is the
union of those intervals inside the window; idle gaps are the rest of
the window, each put down to the benchmark's innermost host span
(``SPANS``) that covers the gap's midpoint, or to ``no span`` where none
does.

The sweep engine's kernel is the one Pallas (Mosaic) custom call of the
sweep's programs.  An operation is taken for it when its instruction or
one of its event's stats names the ``tpu_custom_call`` target, or when
its name, less the ``.<n>`` suffix, is in ``KERNEL_OPS``: the
``pallas_call`` carries no ``name=``, so its custom call is named after
the function that makes the call (``body`` in
``kernels/cgra_sweep/ops.py``, as a traced v5e run shows).

The trace records the host's runtime and the benchmark's spans, not
every Python call: the Python tracer is off, since it costs the host
more than the window's own work.
"""
from __future__ import annotations

import contextlib
import glob
import os
from typing import Dict, List, Tuple

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
SPANS = ("window", "warmup", "campaign", "request")
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
KERNEL_OPS = ("body",)
TOP = 10


@contextlib.contextmanager
def tracing(trace_dir: str):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Merge overlapping ``(start, end)`` intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: List[Tuple[float, float]], lo: float, hi: float):
    """The parts of ``[lo, hi)`` that no merged busy interval covers."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def op_name(event_name: str) -> str:
    """``body.3`` of ``%body.3 = (...) custom-call(...), ...``; a name
    that is no HLO instruction stays as it is."""
    head, eq, _ = event_name.partition(" = ")
    return head.lstrip("%") if eq else event_name


def is_kernel(event_name: str, stats=()) -> bool:
    """Whether an operation event is the sweep engine's kernel."""
    if op_name(event_name).rsplit(".", 1)[0] in KERNEL_OPS:
        return True
    if KERNEL_MARK in event_name:
        return True
    return any(KERNEL_MARK in str(v) for _, v in stats)


def reduce_events(devices: Dict[str, List[Tuple[str, float, float]]],
                  spans: List[Tuple[str, float, float]],
                  kernels=frozenset()) -> dict:
    """The reduction itself, on plain ``(name, start_ns, end_ns)`` events.

    ``devices`` maps a device to its operation events, ``spans`` lists
    the benchmark's host spans; the ``window`` span bounds everything.
    ``kernels`` names the operations that are the sweep engine's kernel.
    """
    win = [s for s in spans if s[0] == "window"]
    if not win:
        raise ValueError("trace has no 'window' span")
    lo, hi = win[0][1], win[0][2]
    inner = [s for s in spans if s[0] != "window"]
    per_device, ops_total, gap_total = [], {}, {}
    for dev in sorted(devices):
        clipped = [(n, max(s, lo), min(e, hi)) for n, s, e in devices[dev]
                   if e > lo and s < hi]
        busy = union([(s, e) for _, s, e in clipped])
        ops: Dict[str, float] = {}
        for n, s, e in clipped:
            ops[n] = ops.get(n, 0.0) + (e - s) * 1e-9
            ops_total[n] = ops_total.get(n, 0.0) + (e - s) * 1e-9
        for s, e in gaps(busy, lo, hi):
            mid = 0.5 * (s + e)
            cover = [sp for sp in inner if sp[1] <= mid < sp[2]]
            who = max(cover, key=lambda sp: sp[1])[0] if cover else "no span"
            gap_total[who] = gap_total.get(who, 0.0) + (e - s) * 1e-9
        per_device.append({"device": dev, "ops_s": ops,
                           "busy_s": sum(e - s for s, e in busy) * 1e-9,
                           "kernel_s": sum(v for k, v in ops.items()
                                           if k in kernels)})
    n = max(len(per_device), 1)
    busy_s = sum(d["busy_s"] for d in per_device) / n
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_s,
        "devices": per_device,
        "device_ops": sorted(([k, v / n] for k, v in ops_total.items()),
                             key=lambda kv: -kv[1])[:TOP],
        "idle_gaps": sorted(([k, v / n] for k, v in gap_total.items()),
                            key=lambda kv: -kv[1])[:TOP],
    }


def read_xplane(path: str, chips: int):
    """``events_of`` one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    return events_of(ProfileData.from_file(path), chips)


def _device_id(plane_name: str):
    """``n`` of a ``/device:TPU:<n>`` plane, else None."""
    rest = plane_name[len(DEVICE_PREFIX):]
    if plane_name.startswith(DEVICE_PREFIX) and rest.isdigit():
        return int(rest)
    return None


def events_of(pd, chips: int):
    """``(devices, spans, kernels)`` of a ``jax.profiler.ProfileData``:
    the ``XLA Ops`` events of devices ``0 .. chips-1``, the benchmark's
    spans from the host planes, and the names of the operations that are
    the sweep engine's kernel."""
    devices, spans, kernels = {}, [], set()
    for plane in pd.planes:
        dev = _device_id(plane.name)
        if dev is not None:
            if dev >= chips:
                continue
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    name = op_name(e.name)
                    evs.append((name, e.start_ns, e.end_ns))
                    if name not in kernels and is_kernel(e.name, e.stats):
                        kernels.add(name)
            devices[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [(e.name, e.start_ns, e.end_ns)
                          for e in line.events if e.name in SPANS]
    return devices, spans, kernels


def summarize(trace_dir: str, chips: int) -> dict:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise ValueError(f"{trace_dir}: expected one xplane file, found "
                         f"{len(paths)}")
    devices, spans, kernels = read_xplane(paths[0], chips)
    if len(devices) != chips:
        raise ValueError(f"{paths[0]}: {len(devices)} {DEVICE_PREFIX}* "
                         f"planes of the first {chips}")
    return reduce_events(devices, spans, kernels)

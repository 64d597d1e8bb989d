"""Program spans and counters of the DSE path.

``span(name, **stats)`` marks one layer boundary as a
``jax.profiler.TraceAnnotation`` named ``repro.<name>``: any
``jax.profiler`` trace then holds it on the host line of the thread that
ran it, on the same clock as the device's operations, with ``stats`` as
event stats (``rid``, ``cid``, ``unit``, ...).  With no trace active a
span costs well under a microsecond, so spans are always on; each wraps
one call of a layer, never a per-lane, per-field, per-step or
per-configuration loop.

``COUNTS`` holds the program's monotone counters of work done, each
incremented once per call: a reader takes their difference over a
window.
"""
from __future__ import annotations

from typing import Dict

import jax

PREFIX = "repro."

COUNTS: Dict[str, float] = {
    # hardware configurations stacked into batched fields
    # (``hwconfig._stack_fields``: ``stack_configs``, ``dse.plan_grid``
    # and the service's admission)
    "hwconfig.configs_stacked": 0,
    # requests the sweep service admitted into a slot, and the seconds
    # they waited in its queue before that (``SweepService._admit``)
    "service.admitted": 0,
    "service.queue_wait_s": 0.0,
    # valid candidates pooled by the host merge of reduced candidate
    # sets (``pareto.merge_reduced`` of two or more parts,
    # ``pareto.fold_segments``), duplicates included
    "pareto.candidates_merged": 0,
}


def span(name: str, **stats) -> jax.profiler.TraceAnnotation:
    """The span ``repro.<name>``; use it as a context manager."""
    return jax.profiler.TraceAnnotation(PREFIX + name, **stats)

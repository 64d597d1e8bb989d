"""The program's spans in a trace (``benchlib.program_spans``): outermost
seconds, a child's time taken out, idle gaps put down to program spans
first, and the readers of spans and counters, on a committed trace and
on a traced window of a cell cut to CPU size."""
import os
import time

import pytest

from benchlib import program_spans, trace_reduce, workload
from benchlib.cell import BENCH_DIR, Cell

from conftest import SEED

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "tiny_trace_spans.pbtxt")
MS = 1e-3


def _pd():
    from jax.profiler import ProfileData
    with open(TRACE) as f:
        return ProfileData.from_text_proto(f.read())


def _read(metric, ctx):
    cell = Cell(name="t", chips=1, config={}, traffic={}, end_to_end=[],
                per_layer=[], bench_dir=BENCH_DIR)
    return cell.reader(metric)(ctx)


def test_program_spans_by_line_and_clipped_to_the_window():
    s = program_spans.reduce(_pd(), 1)
    spans = s["program_spans"]
    assert len(spans) == 14
    assert all(n.startswith("repro.") for n, *_ in spans)
    assert len({ln for *_, ln in spans}) == 2
    # transport.idle [98,105) ends at the window's end
    assert program_spans.seconds(spans, "repro.transport.idle") == \
        pytest.approx(2 * MS)


def test_outermost_seconds_and_a_child_taken_out():
    spans = program_spans.reduce(_pd(), 1)["program_spans"]
    sec = program_spans.seconds
    # the bucket's own dse.sweep lies inside the caller's: counted once
    assert sec(spans, "repro.dse.sweep") == pytest.approx(65 * MS)
    # both plans are outermost of their name
    assert sec(spans, "repro.dse.plan") == pytest.approx(14 * MS)
    # both waits, the nested one too, come out of the sweep
    assert sec(spans, "repro.dse.sweep", less="repro.dse.wait") == \
        pytest.approx(32 * MS)
    assert sec(spans, "repro.runner.unit", less="repro.runner.wait") == \
        pytest.approx(18 * MS)
    # a child on another line is not inside
    assert sec(spans, "repro.runner.unit", less="repro.dse.wait") == \
        pytest.approx(26 * MS)
    assert sec(spans, "repro.service.finish") is None


def test_program_spans_win_the_idle_gaps():
    s = program_spans.reduce(_pd(), 1)
    gaps = dict(s["idle_gaps_by_span"])
    # [4,50) mid 27 -> dse.wait (latest start on line 1);
    # [60,80) mid 70 -> runner.unit (line 1's spans end at 70 or before);
    # [85,90) -> campaign, no program span; [95,100) -> no span
    assert gaps == pytest.approx({"repro.dse.wait": 46 * MS,
                                  "repro.runner.unit": 20 * MS,
                                  "campaign": 5 * MS, "no span": 5 * MS})
    # the accepted reduction reads the same trace as before
    assert dict(s["idle_gaps"]) == pytest.approx({"campaign": 71 * MS,
                                                  "no span": 5 * MS})
    assert s["busy_s"] == pytest.approx(24 * MS)
    # the kernel, named cgra_sweep, is found by its tpu_custom_call target
    assert s["devices"][0]["kernel_s"] == pytest.approx(9 * MS)


def test_innermost_prefers_the_latest_start_then_the_earliest_end():
    spans = [("a", 0, 10), ("b", 2, 9), ("c", 2, 5), ("d", 6, 7)]
    assert program_spans._innermost([1, 3, 5.5, 6.5, 8, 11], spans) == \
        ["a", "c", "b", "d", "b", None]


def test_readers_of_spans_and_counters_on_known_numbers():
    s = program_spans.reduce(_pd(), 1)
    counts = {"hwconfig.configs_stacked": 2400, "service.admitted": 4,
              "service.queue_wait_s": 0.5}
    sweep = {"kind": "sweep", "trace": s, "jobs": 2,
             "obs_counts_delta": counts}
    assert _read("plan_s_per_campaign", sweep) == pytest.approx(7 * MS)
    assert _read("dse_host_s_per_campaign", sweep) == pytest.approx(16 * MS)
    assert _read("configs_stacked_per_campaign", sweep) == 1200
    served = dict(sweep, kind="served", jobs=1)
    assert _read("admit_s_per_request", served) == pytest.approx(3 * MS)
    assert _read("unit_host_s_per_request", served) == pytest.approx(18 * MS)
    assert _read("checkpoint_s_per_request", served) == pytest.approx(6 * MS)
    assert _read("queue_wait_s_per_request", served) == 0.125
    for m in program_spans.METRICS:
        # outside its kind one of the two contexts reads nothing
        assert (_read(m, sweep) is None) != (_read(m, served) is None), m
        # nor without the program's spans and counters in the context
        bare = {"kind": "served" if "request" in m else "sweep",
                "trace": trace_reduce.reduce_events(
                    *trace_reduce.events_of(_pd(), 1)), "jobs": 2}
        assert _read(m, bare) is None, m
    # a real zero is a reading
    zero = dict(served, obs_counts_delta=dict(
        counts, **{"service.queue_wait_s": 0.0}))
    assert _read("queue_wait_s_per_request", zero) == 0.0


@pytest.mark.parametrize("name", ["mibench_t2.sweep", "mibench_t2.served"])
def test_a_traced_window_reads_every_span_metric(name, tiny):
    cell = tiny(name)
    cell.per_layer = []        # no TPU here: the device's readers read nothing
    out = program_spans.measure(cell, SEED, 1.0, time.perf_counter())
    assert out["jobs"] > 0 and not out["failed"]
    kind = cell.traffic["kind"]
    want = {m for m in program_spans.METRICS
            if ("request" in m) == (kind == "served")}
    assert want <= set(out["per_layer"])
    assert all(out["per_layer"][m] >= 0 for m in want)
    assert out["span_events_per_job"] > 0
    if kind == "sweep":
        # H configurations for the campaign's plan, and H more for each
        # of its length buckets
        H = len(workload.hw_grid(cell.config))
        assert out["per_layer"]["configs_stacked_per_campaign"] % H == 0

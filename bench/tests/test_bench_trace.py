"""The reduction from a profiler trace to busy time, top operations and
idle gaps by host span, on a small committed trace."""
import os

import pytest

from benchlib import trace_reduce
from benchlib.cell import BENCH_DIR, Cell

TRACE = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                     "tiny_trace.pbtxt")


def _summary(chips: int = 2):
    from jax.profiler import ProfileData
    with open(TRACE) as f:
        pd = ProfileData.from_text_proto(f.read())
    return trace_reduce.reduce_events(*trace_reduce.events_of(pd, chips))


def _read(metric, ctx):
    cell = Cell(name="t", chips=1, config={}, traffic={}, end_to_end=[],
                per_layer=[], bench_dir=BENCH_DIR)
    return cell.reader(metric)(ctx)


def test_union_and_gaps():
    busy = trace_reduce.union([(5, 8), (0, 2), (1, 3), (8, 9)])
    assert busy == [(0, 3), (5, 9)]
    assert trace_reduce.gaps(busy, 0, 12) == [(3, 5), (9, 12)]
    assert trace_reduce.gaps(busy, 1, 6) == [(3, 5)]
    assert trace_reduce.gaps([], 2, 4) == [(2, 4)]


def test_reduce_events_known_numbers():
    s = _summary()
    assert s["window_s"] == pytest.approx(0.1)
    # dev0 busy: [5,50) + [70,90) = 65 ms; dev1: [10,90) = 80 ms; the
    # "XLA Modules" line and host events other than spans are not ops
    assert s["busy_s"] == pytest.approx((0.065 + 0.080) / 2)
    assert [d["busy_s"] for d in s["devices"]] == pytest.approx(
        [0.065, 0.080])
    ops = dict(s["device_ops"])
    assert set(ops) == {"custom-call.7", "reduce", "early"}
    assert ops["custom-call.7"] == pytest.approx((0.030 + 0.020 + 0.080) / 2)
    assert ops["early"] == pytest.approx(0.007 / 2)
    gaps = dict(s["idle_gaps"])
    # dev0 gaps: [50,70) mid 60 -> no span; [90,105) mid 97.5 -> campaign
    # dev1 gaps: [5,10) mid 7.5 -> no span; [90,105) -> campaign
    assert gaps["no span"] == pytest.approx((0.020 + 0.005) / 2)
    assert gaps["campaign"] == pytest.approx((0.015 + 0.015) / 2)
    assert s["device_ops"][0][0] == "custom-call.7"


def test_kernel_ops():
    assert trace_reduce.op_name(
        "%body.3 = (s32[4096,384]{1,0}) custom-call(s32[1]{0} %b), "
        "custom_call_target=\"tpu_custom_call\"") == "body.3"
    assert trace_reduce.op_name("early") == "early"
    assert trace_reduce.is_kernel("body.3")
    assert trace_reduce.is_kernel(
        "%custom-call.4 = (s32[8]{0}) custom-call(s32[8]{0} %a), "
        "custom_call_target=\"tpu_custom_call\"")
    assert trace_reduce.is_kernel(
        "custom-call.9", [("long_name", 'custom_call_target="tpu_custom_call"')])
    # XLA's own custom calls (gather bounds) and fusions are not the kernel
    assert not trace_reduce.is_kernel("custom-call.1")
    assert not trace_reduce.is_kernel(
        "%while.5 = (s32[]) while((s32[]) %t), body=%wide.body")
    assert not trace_reduce.is_kernel("fusion.12", [("long_name", "fusion")])
    s = _summary()
    assert [d["kernel_s"] for d in s["devices"]] == pytest.approx(
        [0.050, 0.080])


def test_metric_readers_on_the_trace():
    ctx = {"kind": "sweep", "trace": _summary(), "jobs": 2}
    assert _read("device_idle_share.sweep", ctx) == pytest.approx(
        1 - 0.0725 / 0.1)
    # the slowest device's kernel seconds per campaign: 80 ms / 2
    assert _read("kernel_s_per_campaign", ctx) == pytest.approx(0.04)
    assert _read("device_idle_share.served", ctx) is None
    ctx["trace"]["devices"] = [dict(d, kernel_s=0.0)
                               for d in ctx["trace"]["devices"]]
    assert _read("kernel_s_per_campaign", ctx) is None


def test_only_the_cells_chips_count():
    """The trace holds an idle third chip: a two-chip cell leaves it
    out, and a one-chip cell reads its own chip alone."""
    two = _summary(2)
    assert [d["device"] for d in two["devices"]] == ["/device:TPU:0",
                                                     "/device:TPU:1"]
    one = _summary(1)
    assert [d["device"] for d in one["devices"]] == ["/device:TPU:0"]
    assert one["busy_s"] == pytest.approx(0.065)
    three = _summary(3)
    assert three["busy_s"] == pytest.approx((0.065 + 0.080) / 3)


def test_reduce_needs_a_window():
    with pytest.raises(ValueError):
        trace_reduce.reduce_events({"/device:TPU:0": []}, [])

"""Program spans and counters (``repro.obs``) under a real profiler trace.

A reduced, length-bucketed ``dse.sweep``, a drained ``SweepService`` with
checkpoints and one campaign over the HTTP transport each run inside a
``jax.profiler`` trace on the CPU; the ``.xplane.pb`` is read back with
``ProfileData`` and the spans are checked by name, stats and nesting.
"""
import contextlib
import glob
import json
import os

import numpy as np
import pytest

import jax

from repro import obs
from repro.analysis import pareto
from repro.apps import mibench
from repro.core import dse
from repro.core.hwconfig import TOPOLOGIES
from repro.core.program import bucket_programs
from repro.service import SweepClient, SweepRequest, SweepService, \
    SweepTransport

MAX_STEPS = 256
SPEC = pareto.ParetoFront(("latency_cc", "energy_pj"), 8)


@pytest.fixture(scope="module")
def grid(profile):
    ks = [mibench.bitcnt(n_words=16), mibench.crc32(n_words=3)]
    hws = [TOPOLOGIES["baseline"](), TOPOLOGIES["c_interleaved"](),
           TOPOLOGIES["a_fast_mul"]()]
    return dict(programs=[k.program for k in ks], profile=profile,
                hw_configs=hws,
                mem_images=np.stack([k.mem_init for k in ks]),
                max_steps=MAX_STEPS)


@contextlib.contextmanager
def _traced(trace_dir):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def _spans(trace_dir):
    """``(name, start_ns, end_ns, line, stats)`` of every ``repro.*``
    event; ``line`` tells the host threads apart."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for i, line in enumerate(plane.lines):
            out += [(e.name, e.start_ns, e.end_ns, (plane.name, i),
                     dict(e.stats)) for e in line.events
                    if e.name.startswith(obs.PREFIX)]
    return out


def _named(spans, name):
    return [s for s in spans if s[0] == "repro." + name]


def _inside(child, parents):
    """Whether ``child`` lies in one of ``parents`` on its own line."""
    return any(p[3] == child[3] and p[1] <= child[1] and child[2] <= p[2]
               for p in parents)


def test_bucketed_sweep_spans_and_configs_stacked(grid, tmp_path):
    kw = dict(grid, backend="xla", max_buckets=4, reduce=SPEC)
    ref = dse.sweep(**kw)
    n_buckets = bucket_programs(grid["programs"], 4).n_buckets
    assert n_buckets == 2
    stacked0 = obs.COUNTS["hwconfig.configs_stacked"]
    with _traced(tmp_path):
        res = dse.sweep(**kw)
    for f in pareto.REDUCED_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(res, f)),
                                      np.asarray(getattr(ref, f)),
                                      err_msg=f)
    H = len(grid["hw_configs"])
    # one plan of the whole grid, then one per length bucket
    assert (obs.COUNTS["hwconfig.configs_stacked"] - stacked0
            == H * (1 + n_buckets))

    spans = _spans(tmp_path)
    names = ("dse.sweep", "dse.plan", "hwconfig.stack", "dse.bucket",
             "dse.dispatch", "dse.wait", "dse.merge")
    for name in names:
        assert _named(spans, name), name
    sweeps = _named(spans, "dse.sweep")
    outer = min(sweeps, key=lambda s: s[1])
    assert outer[4] == {"G": 2, "H": H, "D": 2}
    assert len(sweeps) == 1 + n_buckets
    assert sorted(s[4]["bucket"] for s in _named(spans, "dse.bucket")) \
        == list(range(n_buckets))
    assert [s[4]["n"] for s in _named(spans, "hwconfig.stack")] \
        == [H] * (1 + n_buckets)
    assert all(_inside(s, _named(spans, "dse.plan"))
               for s in _named(spans, "hwconfig.stack"))
    assert all(_inside(s, sweeps) for s in _named(spans, "dse.wait"))
    # each bucket's sweep nests in its bucket span, inside the caller's
    assert all(_inside(s, _named(spans, "dse.bucket"))
               for s in sweeps if s is not outer)
    assert all(_inside(s, [outer]) for s in spans if s[3] == outer[3])


def test_drained_service_spans_and_counters(grid, tmp_path):
    svc = SweepService(grid["profile"], slots=1, unit_size=2,
                       max_steps=MAX_STEPS, ckpt_root=str(tmp_path / "ck"))
    reqs = [SweepRequest(programs=[p], hw_configs=grid["hw_configs"],
                         mem_images=grid["mem_images"][g:g + 1],
                         reduce=SPEC)
            for g, p in enumerate(grid["programs"])]
    admitted0 = obs.COUNTS["service.admitted"]
    wait0 = obs.COUNTS["service.queue_wait_s"]
    with _traced(tmp_path / "trace"):
        for r in reqs:
            svc.submit(r)
        out = svc.drain()
    assert set(out) == {r.rid for r in reqs}
    assert obs.COUNTS["service.admitted"] - admitted0 == len(reqs)
    assert obs.COUNTS["service.queue_wait_s"] - wait0 >= 0

    spans = _spans(tmp_path / "trace")
    admits = _named(spans, "service.admit")
    assert sorted(rid for s in admits for rid in json.loads(s[4]["rids"])) \
        == [r.rid for r in reqs]
    units = _named(spans, "runner.unit")
    assert units and all("unit" in s[4] for s in units)
    for name in ("runner.wait", "runner.checkpoint"):
        assert all(_inside(s, units) for s in _named(spans, name)), name
    assert len(_named(spans, "runner.checkpoint")) == len(units)
    assert len(_named(spans, "service.finish")) == len(admits)


def test_transport_and_client_spans_share_the_campaign_id(grid, tmp_path):
    svc = SweepService(grid["profile"], unit_size=2, max_steps=MAX_STEPS,
                       mem_size=int(grid["mem_images"].shape[1]))
    transport = SweepTransport(svc)
    host, port = transport.start()
    try:
        with _traced(tmp_path):
            res = SweepClient(host, port).sweep(
                grid["programs"], grid["hw_configs"], grid["mem_images"],
                reduce=SPEC)
    finally:
        transport.close()
    assert res.stats.records_folded > 0
    spans = _spans(tmp_path)
    for name in ("transport.submit", "transport.step", "service.admit",
                 "runner.unit"):
        assert _named(spans, name), name
    sends = _named(spans, "transport.send")
    folds = _named(spans, "client.fold")
    # one fold per record, and the final merge
    assert len(folds) == res.stats.records_folded + 1
    cids = {s[4]["cid"] for s in sends + folds}
    assert len(cids) == 1
    # the service's rid of that campaign is the number in its cid
    rids = {json.loads(s[4]["rids"])[0]
            for s in _named(spans, "service.admit")}
    assert {f"c{r}" for r in rids} == cids

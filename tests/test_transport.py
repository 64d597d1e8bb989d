"""Chaos-hardened HTTP transport for the sweep service.

The headline contract: a campaign driven through ``SweepClient`` over a
*faulty* transport (dropped submit responses, mid-stream disconnects,
duplicate delivery, a server SIGTERM drain + restart) folds to results
bit-identical to the same grid swept monolithically via ``dse.sweep``
-- discrete fields exact everywhere, float accumulators ULP-tight
across compiled batch shapes (the repo-wide comparison convention, see
test_sweep_service.py).

Also here: the autotune read-merge-write file lock (racing writers no
longer drop each other's entries) and the ``steps_history`` LRU bound.
"""
import http.client
import json
import os
import signal
import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from repro.analysis import pareto
from repro.apps import mibench
from repro.core import dse
from repro.core.autotune import AutotuneCache, ShapeClass, TunedConfig
from repro.core.hwconfig import TOPOLOGIES, HwConfig
from repro.runtime.faults import FAULT_PLAN_ENV, FaultPlan, NetFaultInjector
from repro.service import (ClientRetry, SweepClient, SweepRequest,
                           SweepService, SweepTransport)
from repro.service.runner import RESULT_FIELDS, _RESULT_DTYPES
from repro.service.transport import (hw_from_wire, hw_to_wire,
                                     program_from_wire, program_to_wire,
                                     sweep_to_wire)

MAX_STEPS = 256          # one compiled shape shared by every test here
DISCRETE = ("latency_cc", "checksum", "steps_executed")


@pytest.fixture(scope="module")
def grid(profile):
    ks = [mibench.bitcnt(n_words=16), mibench.crc32(n_words=3)]
    hws = [TOPOLOGIES["baseline"](), TOPOLOGIES["c_interleaved"]()]
    mems = np.stack([k.mem_init for k in ks])
    return dict(programs=[k.program for k in ks], profile=profile,
                hw_configs=hws, mem_images=mems, max_steps=MAX_STEPS)


@pytest.fixture(scope="module")
def mono(grid):
    """The uninterrupted single-call reference sweep (B = 2*2*2 = 8)."""
    return dse.sweep(**grid)


def _service(grid, **kw):
    kw.setdefault("unit_size", 2)
    return SweepService(grid["profile"], max_steps=MAX_STEPS,
                        mem_size=int(grid["mem_images"].shape[1]), **kw)


def _start(grid, injector=None, **kw):
    t = SweepTransport(_service(grid, **kw), injector=injector)
    t.start()
    return t


def _body(grid, key, **kw):
    return {"v": 1, "idempotency_key": key,
            "sweep": sweep_to_wire(grid["programs"], grid["hw_configs"],
                                   grid["mem_images"], **kw)}


def _assert_matches_mono(mono, arrays):
    for f in DISCRETE:
        np.testing.assert_array_equal(
            arrays[f], np.asarray(getattr(mono, f)), err_msg=f)
    for f in ("energy_pj", "power_mw"):
        np.testing.assert_allclose(
            arrays[f], np.asarray(getattr(mono, f)), rtol=1e-6, err_msg=f)


# ---------------------------------------------------------------------------
# Wire codecs
# ---------------------------------------------------------------------------

def test_wire_codecs_bit_exact_roundtrip(grid):
    """Arrays travel as base64 raw bytes: every dtype round-trips
    bit-for-bit through actual JSON text (floats included -- no decimal
    detour), programs re-validate, hw configs keep their field values,
    and a real ReducedResult survives whole."""
    a = np.array([1.5, -0.0, np.pi, 1e-38], np.float32)
    b = pareto.array_from_wire(
        json.loads(json.dumps(pareto.array_to_wire(a))))
    assert b.dtype == a.dtype and b.tobytes() == a.tobytes()

    p = grid["programs"][1]
    q = program_from_wire(json.loads(json.dumps(program_to_wire(p))))
    assert q.name == p.name
    for f in ("ops", "dest", "srcA", "srcB", "imm"):
        np.testing.assert_array_equal(getattr(q, f), getattr(p, f))

    c = grid["hw_configs"][1]
    c2 = hw_from_wire(json.loads(json.dumps(hw_to_wire(c))))
    for f in HwConfig.FIELDS:
        assert np.asarray(getattr(c2, f)).item() \
            == np.asarray(getattr(c, f)).item()

    spec = pareto.TopK(objective="edp", k=3)
    red = dse.sweep(**grid, reduce=spec)
    red2 = pareto.reduced_from_wire(
        json.loads(json.dumps(pareto.reduced_to_wire(red))))
    for f in pareto.REDUCED_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(red, f)),
                                      np.asarray(getattr(red2, f)),
                                      err_msg=f)


# ---------------------------------------------------------------------------
# Clean transport == monolithic
# ---------------------------------------------------------------------------

def test_transport_matches_monolithic(grid, mono):
    """Submit + stream + fold over a clean wire reproduces the
    monolithic sweep; health endpoints answer."""
    t = _start(grid)
    try:
        client = SweepClient(t.host, t.port, seed=1)
        assert client.healthz() and client.readyz()
        res = client.sweep(grid["programs"], grid["hw_configs"],
                           grid["mem_images"])
        assert not res.expired and res.skipped_lanes == 0
        assert res.stats.records_folded == 4          # 8 lanes / unit 2
        assert res.stats.resubmits == 0
        _assert_matches_mono(mono, res.arrays)
    finally:
        t.close()


def test_transport_reduced_matches_monolithic(grid):
    """A reduced campaign's folded partial stream equals the solo
    reduced sweep (indices/count/discrete exact -- the reduced
    comparison contract)."""
    spec = pareto.TopK(objective="edp", k=4)
    solo = dse.sweep(**grid, reduce=spec)
    t = _start(grid)
    try:
        client = SweepClient(t.host, t.port, seed=1)
        res = client.sweep(grid["programs"], grid["hw_configs"],
                           grid["mem_images"], reduce=spec)
        red = res.reduced()
        for f in ("indices", "count") + DISCRETE:
            np.testing.assert_array_equal(np.asarray(getattr(red, f)),
                                          np.asarray(getattr(solo, f)),
                                          err_msg=f)
    finally:
        t.close()


# ---------------------------------------------------------------------------
# Idempotent submission + backpressure + error mapping
# ---------------------------------------------------------------------------

def test_idempotent_submission_replays_campaign(grid):
    """Replaying a POST under the same idempotency key returns the
    existing campaign (created=false) -- at-most-one admission no
    matter how many times the submit is retried."""
    t = _start(grid)
    try:
        client = SweepClient(t.host, t.port)
        body = _body(grid, "k-replay")
        s1, o1 = client._request("POST", "/v1/sweeps", body)
        s2, o2 = client._request("POST", "/v1/sweeps", body)
        assert (s1, o1["created"]) == (201, True)
        assert (s2, o2["created"]) == (200, False)
        assert o1["campaign"] == o2["campaign"]
    finally:
        t.close()


def test_submission_error_mapping(grid):
    """Queue-full -> 429 + Retry-After; malformed body -> 400; unknown
    campaign -> 404 (status and stream alike)."""
    t = _start(grid, queue_max=0)          # every submit overloads
    try:
        client = SweepClient(t.host, t.port)
        conn = http.client.HTTPConnection(t.host, t.port, timeout=10)
        conn.request("POST", "/v1/sweeps",
                     json.dumps(_body(grid, "k-429")).encode())
        r = conn.getresponse()
        assert r.status == 429 and r.getheader("Retry-After")
        conn.close()
        assert client._request(
            "POST", "/v1/sweeps",
            {"v": 1, "idempotency_key": "x"})[0] == 400   # no sweep body
        assert client._request(
            "POST", "/v1/sweeps", {"sweep": {}})[0] == 400  # no key
        assert client._request("GET", "/v1/sweeps/nope")[0] == 404
        assert client._request("GET", "/v1/sweeps/nope/stream")[0] == 404
    finally:
        t.close()


# ---------------------------------------------------------------------------
# Chaos over the wire: drop + disconnect + duplicate
# ---------------------------------------------------------------------------

def test_chaos_transport_folds_bit_identical(grid, mono):
    """Dropped submit responses + a disconnect after every record +
    50% duplicate delivery: the folded answer is unchanged, and the
    client stats prove each fault class actually fired."""
    plan = FaultPlan(seed=7, net_submit_drop_rate=1.0,
                     net_max_submit_drops=2,
                     net_stream_disconnect_every=1,
                     net_duplicate_rate=0.5)
    t = _start(grid, injector=NetFaultInjector(plan))
    try:
        client = SweepClient(t.host, t.port, seed=3)
        res = client.sweep(grid["programs"], grid["hw_configs"],
                           grid["mem_images"])
        _assert_matches_mono(mono, res.arrays)
        st = res.stats
        assert st.submit_attempts >= 3         # 2 dropped responses
        assert st.reconnects >= 3              # cut after every record
        assert st.duplicate_records >= 1       # replays folded anyway
    finally:
        t.close()


def test_chaos_duplicate_delivery_reduced_idempotent(grid):
    """End-to-end merge_reduced idempotency over the wire: every record
    duplicated, disconnects forcing whole-suffix replays -- the reduced
    fold still equals the solo sweep exactly."""
    spec = pareto.ParetoFront(axes=("latency_cc", "energy_pj"),
                              max_points=8)
    solo = dse.sweep(**grid, reduce=spec)
    plan = FaultPlan(seed=11, net_stream_disconnect_every=2,
                     net_duplicate_rate=1.0)
    t = _start(grid, injector=NetFaultInjector(plan))
    try:
        client = SweepClient(t.host, t.port, seed=5)
        res = client.sweep(grid["programs"], grid["hw_configs"],
                           grid["mem_images"], reduce=spec)
        assert res.stats.duplicate_records >= 1
        red = res.reduced()
        for f in ("indices", "count", "clipped") + DISCRETE:
            np.testing.assert_array_equal(np.asarray(getattr(red, f)),
                                          np.asarray(getattr(solo, f)),
                                          err_msg=f)
    finally:
        t.close()


def test_midstream_kill_resumes_from_cursor(grid, mono):
    """A client killed between acked records resumes at its cursor: the
    second connection re-delivers nothing already acked (zero duplicate
    folds) and the stitched result is still complete and exact."""
    t = _start(grid)
    try:
        client = SweepClient(t.host, t.port)
        s, obj = client._request("POST", "/v1/sweeps",
                                 _body(grid, "k-cursor"))
        assert s == 201
        cid = obj["campaign"]
        arrays = {f: np.zeros(8, _RESULT_DTYPES[f]) for f in RESULT_FIELDS}

        def fold(msg):
            lo, hi = msg["lo"], msg["hi"]
            for f in RESULT_FIELDS:
                arrays[f][lo:hi] = pareto.array_from_wire(msg["arrays"][f])

        # first client life: ack exactly two records, then die abruptly
        first = []
        for msg in client._stream_once(cid, 0):
            if "arrays" in msg:
                first.append(msg["cursor"])
                fold(msg)
                if len(first) == 2:
                    break
        assert first == [0, 1]
        # second life resumes at cursor=2; nothing acked is re-sent
        second = []
        for msg in client._stream_once(cid, 2):
            if "arrays" in msg:
                second.append(msg["cursor"])
                fold(msg)
        assert second == [2, 3]               # zero duplicate folds
        for f in DISCRETE:
            np.testing.assert_array_equal(
                arrays[f], np.asarray(getattr(mono, f)), err_msg=f)
    finally:
        t.close()


# ---------------------------------------------------------------------------
# The acceptance drill (subprocess, both backends): execution transients
# + network drop/disconnect/duplicate + one SIGTERM drain/restart, and
# the folded answer is bit-identical to the monolithic dse.sweep.
# ---------------------------------------------------------------------------

DRILL_PLAN = FaultPlan(seed=13, transient_rate=0.6,
                       max_transient_per_unit=2,
                       net_submit_drop_rate=0.5, net_max_submit_drops=1,
                       net_stream_disconnect_every=2,
                       net_duplicate_rate=0.5)
DRILL_MEM = 4096


def _serve(port_file, ckpt_root, backend, port=0):
    env = dict(os.environ, PYTHONPATH="src")
    env[FAULT_PLAN_ENV] = DRILL_PLAN.to_json()
    return subprocess.Popen(
        [sys.executable, "-m", "repro.service", "serve",
         "--port", str(port), "--port-file", str(port_file),
         "--unit-size", "1", "--max-steps", str(MAX_STEPS),
         "--mem-size", str(DRILL_MEM), "--backend", backend,
         "--ckpt-root", str(ckpt_root)],
        cwd=str(Path(__file__).resolve().parents[1]), env=env,
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT)


def _wait_port(port_file, proc, timeout=300.0):
    t0 = time.monotonic()
    while time.monotonic() - t0 < timeout:
        if port_file.exists():
            d = json.loads(port_file.read_text())
            return d["host"], d["port"]
        if proc.poll() is not None:
            raise AssertionError("server died before binding:\n"
                                 + proc.stdout.read().decode())
        time.sleep(0.05)
    raise AssertionError("server never wrote its port file")


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_chaos_drain_restart_bit_identical(tmp_path, profile, backend):
    """The full drill: a chaos server (injected execution transients +
    network drop/disconnect/duplicate) is SIGTERMed mid-campaign; it
    drains gracefully (exit 0, in-flight unit checkpointed); the client
    rides the cut, re-submits under the same idempotency key to a
    restarted server on the same port + checkpoint root (which resumes
    the completed units from disk), and the folded result is
    bit-identical to the monolithic ``dse.sweep``."""
    ks = [mibench.bitcnt(n_words=16), mibench.crc32(n_words=3)]
    hws = [TOPOLOGIES["baseline"](), TOPOLOGIES["c_interleaved"]()]
    mems = np.stack([k.mem_init for k in ks])
    progs = [k.program for k in ks]

    port_file, ckpt_root = tmp_path / "port.json", tmp_path / "ck"
    srv = _serve(port_file, ckpt_root, backend)
    host, port = _wait_port(port_file, srv)

    client = SweepClient(host, port, seed=17, timeout_s=60.0,
                         retry=ClientRetry(max_attempts=60,
                                           max_resubmits=8,
                                           max_backoff_s=1.0))
    result = {}

    def drive():
        try:
            result["res"] = client.sweep(progs, hws, mems,
                                         idempotency_key="drill-1")
        except BaseException as e:               # surfaced after join
            result["err"] = e

    th = threading.Thread(target=drive)
    th.start()
    # SIGTERM once the campaign has streamed >= 1 record but is not yet
    # done (the injected transients' real backoff sleeps hold that
    # window open); c0 is the first admitted campaign
    deadline = time.monotonic() + 300
    while time.monotonic() < deadline:
        try:
            s, o = client._request("GET", "/v1/sweeps/c0")
            if s == 200 and o.get("records", 0) >= 1 \
                    and o.get("status") == "running":
                break
        except (OSError, http.client.HTTPException):
            pass
        time.sleep(0.02)
    srv.send_signal(signal.SIGTERM)
    assert srv.wait(timeout=300) == 0
    assert "drained" in srv.stdout.read().decode()

    # restart on the SAME port with the SAME checkpoint root
    srv2 = _serve(port_file, ckpt_root, backend, port=port)
    try:
        th.join(timeout=600)
        assert not th.is_alive(), "client never completed after restart"
        if "err" in result:
            raise result["err"]
        res = result["res"]
        assert res.stats.resubmits >= 1       # rode the drain/restart
        mono = dse.sweep(programs=progs, profile=profile, hw_configs=hws,
                         mem_images=mems, max_steps=MAX_STEPS,
                         mem_size=DRILL_MEM, backend=backend)
        _assert_matches_mono(mono, res.arrays)
    finally:
        srv2.send_signal(signal.SIGTERM)
        srv2.wait(timeout=300)


# ---------------------------------------------------------------------------
# Service ckpt_root: completed units survive a restart
# ---------------------------------------------------------------------------

def test_service_ckpt_root_resumes_completed_units(grid, tmp_path, mono):
    """An identical re-submission against the same checkpoint root
    resumes its completed units from disk: their partials are replayed
    at admission (a streaming client folds a complete set), only the
    remaining units are computed, and the answer matches the monolithic
    sweep."""
    root = str(tmp_path / "ck")

    def request(partials):
        return SweepRequest(
            programs=grid["programs"], hw_configs=grid["hw_configs"],
            mem_images=grid["mem_images"],
            on_partial=lambda rid, lo, hi, a: partials.append((lo, hi)))

    s1 = _service(grid, ckpt_root=root)
    p1 = []
    s1.submit(request(p1))
    s1.step()                            # admit + unit 0
    s1.step()                            # unit 1
    s1._slots[0].runner.mgr.wait()       # make the async saves durable
    assert p1 == [(0, 2), (2, 4)]

    s2 = _service(grid, ckpt_root=root)
    p2 = []
    rid = s2.submit(request(p2))
    s2.step()
    # admission replayed the two checkpointed units, then ran one more
    assert p2 == [(0, 2), (2, 4), (4, 6)]
    res = s2.drain()[rid]
    assert sorted(set(p2)) == [(0, 2), (2, 4), (4, 6), (6, 8)]
    for f in DISCRETE:
        np.testing.assert_array_equal(
            res.arrays[f], np.asarray(getattr(mono, f)), err_msg=f)


# ---------------------------------------------------------------------------
# steps_history LRU bound (satellite)
# ---------------------------------------------------------------------------

def test_steps_history_lru_bounded(grid):
    """The per-kernel trip-count history is LRU-bounded: pushing more
    names than the cap evicts the least recently touched, and a
    recency-refreshed entry survives the next insertion."""
    svc = _service(grid, steps_history_max=2)
    svc.steps_history["a"] = 10
    svc.steps_history["b"] = 20
    svc._record_steps(
        SweepRequest(programs=grid["programs"][:1],
                     hw_configs=grid["hw_configs"],
                     mem_images=grid["mem_images"][:1]),
        {"steps_executed": np.full((2,), 7, np.int32)}, reduced=False)
    name0 = grid["programs"][0].name
    assert list(svc.steps_history) == ["b", name0]   # "a" evicted
    # refreshing "b" then inserting another evicts the kernel, not "b"
    svc.steps_history.move_to_end("b")
    svc._record_steps(
        SweepRequest(programs=grid["programs"][1:],
                     hw_configs=grid["hw_configs"],
                     mem_images=grid["mem_images"][:1]),
        {"steps_executed": np.full((2,), 9, np.int32)}, reduced=False)
    assert list(svc.steps_history) == ["b", grid["programs"][1].name]


# ---------------------------------------------------------------------------
# Autotune cross-process cache warming (satellite)
# ---------------------------------------------------------------------------

def _cfg(n):
    return TunedConfig(blk_b=16 + n, chunk_steps=32, max_buckets=2,
                       source="tuned", points_per_s=1.0)


def _shape(n):
    return ShapeClass(G=n, t_max=8, H=2, D=2, backend="xla")


def test_autotune_save_merges_concurrent_writers(tmp_path):
    """The last-writer-wins regression: two caches loaded before either
    saved used to drop each other's entries; read-merge-write under the
    file lock keeps both."""
    path = tmp_path / "autotune.json"
    c1, c2 = AutotuneCache(path), AutotuneCache(path)   # both load empty
    c1.store(_shape(1), _cfg(1))
    c2.store(_shape(2), _cfg(2))       # used to clobber c1's entry
    on_disk = AutotuneCache(path)
    assert _shape(1).key in on_disk.entries
    assert _shape(2).key in on_disk.entries
    # the merging writer also warmed its own in-memory view
    assert _shape(1).key in c2.entries


def test_autotune_racing_writers_keep_every_entry(tmp_path):
    """Racing writer threads with disjoint key sets and interleaved
    saves: every entry survives."""
    path = tmp_path / "autotune.json"
    N = 12

    def writer(base):
        cache = AutotuneCache(path)
        for i in range(N):
            cache.store(_shape(base + i), _cfg(i))

    ts = [threading.Thread(target=writer, args=(b,)) for b in (100, 200)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    final = AutotuneCache(path)
    missing = [b + i for b in (100, 200) for i in range(N)
               if _shape(b + i).key not in final.entries]
    assert not missing, f"racing writers dropped entries: {missing}"


def test_autotune_lock_timeout_falls_back(tmp_path):
    """A held lock degrades the save to the plain atomic write instead
    of blocking: the cache is an accelerator, never a contention
    point."""
    fcntl = pytest.importorskip("fcntl")
    path = tmp_path / "autotune.json"
    cache = AutotuneCache(path, lock_timeout_s=0.1)
    fd = os.open(str(path) + ".lock", os.O_CREAT | os.O_RDWR)
    try:
        fcntl.flock(fd, fcntl.LOCK_EX)
        t0 = time.monotonic()
        cache.store(_shape(5), _cfg(5))          # must not deadlock
        assert time.monotonic() - t0 < 5.0
    finally:
        os.close(fd)
    assert _shape(5).key in AutotuneCache(path).entries

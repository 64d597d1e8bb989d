"""Crash-safe DSE sweep service: unit partitioning, checkpoint/resume,
retry/degradation, fleet wiring, request packing -- and the headline
contract: a SIGKILLed campaign resumes bit-identical to an uninterrupted
run, on both backends."""
import json
import os
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.apps import mibench
from repro.core import dse
from repro.core.hwconfig import TOPOLOGIES
from repro.runtime import StragglerPolicy
from repro.runtime.faults import (FAULT_PLAN_ENV, FaultInjector, FaultPlan)
from repro.service import (CheckpointMismatch, FleetMonitor,
                           ResumableSweepRunner, RetryPolicy, ServiceOverloaded,
                           SweepRequest, SweepService, SweepUnitError,
                           backend_chain)

MAX_STEPS = 256          # one compiled shape shared by every test here


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


DISCRETE = ("latency_cc", "checksum", "steps_executed")


def _assert_same(a, b, fields=None):
    """Exact equality on every field."""
    for f in fields or a._fields:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)), err_msg=f)


def _assert_matches_mono(mono, res):
    """Cross-shape comparison (monolithic B=8 executable vs padded-unit
    executables): cycle counts/checksums/step counts are exact; float32
    energy/power accumulators may differ by rounding when XLA compiles a
    different batch shape, so those get an ULP-tight allclose."""
    _assert_same(mono, res, fields=DISCRETE)
    for f in ("energy_pj", "power_mw"):
        np.testing.assert_allclose(np.asarray(getattr(mono, f)),
                                   np.asarray(getattr(res, f)),
                                   rtol=1e-6, err_msg=f)


# ---------------------------------------------------------------------------
# Partitioned execution == monolithic execution
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("unit_size", [1, 3, 8, 64])
def test_unit_partition_matches_monolithic(grid, mono, unit_size):
    """Any unit partition (including ragged tail + padded units) stitches
    to the monolithic result -- lanes are independent.  (Discrete fields
    exact; float accumulators ULP-tight across the different compiled
    batch shapes.)  Two runs of the SAME partition are bit-identical --
    the contract the kill-and-resume tests build on."""
    res, rep = ResumableSweepRunner(unit_size=unit_size, **grid).run()
    _assert_matches_mono(mono, res)
    assert rep.units_run == rep.units_total == -(-8 // unit_size)
    again, _ = ResumableSweepRunner(unit_size=unit_size, **grid).run()
    _assert_same(res, again)


def test_pallas_backend_partition_matches_monolithic(grid):
    pall = dict(grid, backend="pallas")
    mono_p = dse.sweep(**pall)
    res, _ = ResumableSweepRunner(unit_size=3, **pall).run()
    _assert_matches_mono(mono_p, res)


def test_units_share_one_compiled_executable(grid):
    """Zero retrace across units: the whole partitioned campaign costs
    the same number of traces as one monolithic make_sweep_fn call."""
    runner = ResumableSweepRunner(unit_size=2, **grid)
    before = dict(dse.TRACE_COUNTS)
    runner.run()
    traced = dse.TRACE_COUNTS["xla"] - before["xla"]
    assert traced <= 1, f"{traced} traces for 4 units (expected <= 1)"


# ---------------------------------------------------------------------------
# Checkpoint / resume
# ---------------------------------------------------------------------------

def test_resume_skips_completed_units(grid, mono, tmp_path):
    r1 = ResumableSweepRunner(ckpt_dir=str(tmp_path), unit_size=3, **grid)
    r1.run_unit(0)
    r1.run_unit(1)
    r1.mgr.wait()
    r2 = ResumableSweepRunner(ckpt_dir=str(tmp_path), unit_size=3, **grid)
    assert r2.pending_units() == [2]
    res, rep = r2.run()
    assert rep.units_resumed == 2 and rep.units_run == 1
    uninterrupted, _ = ResumableSweepRunner(unit_size=3, **grid).run()
    _assert_same(uninterrupted, res)       # bit-identical, every field
    _assert_matches_mono(mono, res)


def test_checkpoint_fingerprint_mismatch_refused(grid, tmp_path):
    """A checkpoint directory from a different campaign (other config)
    must be refused, not silently stitched."""
    r1 = ResumableSweepRunner(ckpt_dir=str(tmp_path), unit_size=3, **grid)
    r1.run_unit(0)
    r1.mgr.wait()
    other = dict(grid, max_steps=MAX_STEPS // 2)
    with pytest.raises(CheckpointMismatch, match="fingerprint"):
        ResumableSweepRunner(ckpt_dir=str(tmp_path), unit_size=3, **other)


# ---------------------------------------------------------------------------
# Retry / backoff / degradation
# ---------------------------------------------------------------------------

def test_transient_faults_absorbed_by_retry(grid, mono):
    """A campaign with injected transient failures (capped per unit)
    completes with the exact reference result; backoff sleeps follow the
    exponential schedule."""
    sleeps = []
    inj = FaultInjector(FaultPlan(seed=3, transient_rate=1.0,
                                  max_transient_per_unit=2))
    r = ResumableSweepRunner(
        unit_size=3, injector=inj, sleep=sleeps.append,
        retry=RetryPolicy(max_attempts=3, backoff_s=0.01, backoff_mult=2.0),
        **grid)
    res, rep = r.run()
    clean, _ = ResumableSweepRunner(unit_size=3, **grid).run()
    _assert_same(clean, res)               # faults never change results
    # 2 transients + 1 success per unit, with backoff 0.01 then 0.02
    assert rep.attempts_total == 3 * rep.units_total
    assert sleeps == [0.01, 0.02] * rep.units_total
    assert not rep.degraded


def test_retry_exhaustion_raises(grid):
    """Transients beyond max_attempts (and no degradation rung left on
    xla) surface as SweepUnitError, not silence."""
    inj = FaultInjector(FaultPlan(transient_rate=1.0,
                                  max_transient_per_unit=99))
    r = ResumableSweepRunner(unit_size=3, injector=inj,
                             sleep=lambda s: None,
                             retry=RetryPolicy(max_attempts=2), **grid)
    with pytest.raises(SweepUnitError, match="every backend"):
        r.run()


def test_degradation_chain_order():
    assert [s.name for s in backend_chain("pallas")] \
        == ["pallas", "pallas_interpret", "xla"]
    assert [s.name for s in backend_chain("pallas", interpret=True)] \
        == ["pallas_interpret", "xla"]
    assert [s.name for s in backend_chain("xla")] == ["xla"]


def test_degradation_chain_on_tpu_never_interprets(monkeypatch):
    """On a TPU the interpreter must not hide a failing compiled kernel:
    the chain is compiled Pallas -> XLA, and interpret=True is refused."""
    import jax
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert [s.name for s in backend_chain("pallas")] == ["pallas", "xla"]
    assert [s.name for s in backend_chain("pallas", interpret=False)] \
        == ["pallas", "xla"]
    with pytest.raises(ValueError, match="interpreter"):
        backend_chain("pallas", interpret=True)


def test_persistent_backend_failure_degrades_to_xla(grid, mono):
    """Both Pallas rungs broken -> every unit lands on the XLA rung,
    recorded in report.degraded, and the discrete outputs still match
    the reference."""
    inj = FaultInjector(FaultPlan(
        broken_backends=("pallas", "pallas_interpret")))
    r = ResumableSweepRunner(unit_size=3, injector=inj,
                             sleep=lambda s: None,
                             **dict(grid, backend="pallas"))
    res, rep = r.run()
    assert set(rep.degraded) == {0, 1, 2}
    assert set(rep.degraded.values()) == {"xla"}
    _assert_same(mono, res, fields=DISCRETE)


def test_mixed_chaos_campaign_completes(grid, mono):
    """The acceptance scenario: 20% transient rate + one persistently
    broken backend stage; the campaign completes, results are exact, and
    the degraded units are reported."""
    inj = FaultInjector(FaultPlan(seed=11, transient_rate=0.2,
                                  broken_backends=("pallas",)))
    r = ResumableSweepRunner(unit_size=2, injector=inj,
                             sleep=lambda s: None,
                             **dict(grid, backend="pallas"))
    res, rep = r.run()
    assert set(rep.degraded) == set(range(rep.units_total))
    assert set(rep.degraded.values()) == {"pallas_interpret"}
    _assert_same(mono, res, fields=DISCRETE)


# ---------------------------------------------------------------------------
# Fleet wiring: heartbeats -> replan; stragglers -> rebalance
# ---------------------------------------------------------------------------

def test_dead_node_triggers_replan_and_exact_resume(grid, mono):
    """A worker that stops heartbeating is confirmed failed and dropped
    by an elastic re-plan; the remaining units complete and the stitched
    result is unchanged."""
    t = {"now": 0.0}
    mon = FleetMonitor(["w0", "w1"], clock=lambda: t["now"], timeout=5.0)
    inj = FaultInjector(FaultPlan(dead_nodes=((1, "w1"),)))
    r = ResumableSweepRunner(unit_size=2, monitor=mon, injector=inj,
                             **grid)
    for k in r.pending_units():
        r.run_unit(k)
        t["now"] += 6.0
    assert r.report.replans
    assert r.report.replans[0]["dropped"] == ["w1"]
    assert mon.nodes == ["w0"]
    clean, _ = ResumableSweepRunner(unit_size=2, **grid).run()
    _assert_same(clean, r.stitch())


def test_all_workers_dead_raises(grid):
    t = {"now": 0.0}
    mon = FleetMonitor(["w0"], clock=lambda: t["now"], timeout=5.0)
    inj = FaultInjector(FaultPlan(dead_nodes=((0, "w0"),)))
    r = ResumableSweepRunner(unit_size=2, monitor=mon, injector=inj,
                             **grid)
    r.run_unit(0)
    t["now"] = 10.0
    with pytest.raises(SweepUnitError, match="every worker"):
        r.run_unit(1)


def test_straggler_feeds_unit_size_rebalance(grid):
    """A persistently slow worker escalates rebalance -> replace and the
    report suggests halving the unit size for the next campaign."""
    mon = FleetMonitor(["w0", "w1", "w2"],
                       policy=StragglerPolicy(persistent_k=2,
                                              min_samples=3))
    inj = FaultInjector(FaultPlan(slow_units=(1,), slow_extra_s=50.0))
    r = ResumableSweepRunner(unit_size=2, monitor=mon, injector=inj,
                             **grid)
    _, rep = r.run()
    acts = [(a["node"], a["action"]) for a in rep.straggler_actions]
    assert ("w1", "rebalance") in acts and ("w1", "replace") in acts
    assert rep.suggested_unit_size == 1


def test_straggler_policies_not_shared_between_monitors():
    """Regression: StragglerDetector used to share one mutable policy
    object across instances (mutable default argument)."""
    a = FleetMonitor(["n0"])
    b = FleetMonitor(["n0"])
    a.straggler.policy.z_threshold = 99.0
    assert b.straggler.policy.z_threshold != 99.0


# ---------------------------------------------------------------------------
# Kill-and-resume (subprocess, SIGKILL): the headline contract
# ---------------------------------------------------------------------------

def _run_cli(tmp_path, out, extra_args=(), fault_plan=None):
    env = dict(os.environ, PYTHONPATH="src")
    if fault_plan is not None:
        env[FAULT_PLAN_ENV] = fault_plan.to_json()
    return subprocess.run(
        [sys.executable, "-m", "repro.service",
         "--kernels", "bitcnt,crc32", "--unit-size", "3",
         "--max-steps", str(MAX_STEPS), "--out", str(out), *extra_args],
        env=env, cwd="/root/repo", capture_output=True, text=True)


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_sigkill_midsweep_resumes_bit_identical(tmp_path, backend):
    """SIGKILL the campaign right before a unit's checkpoint commit (the
    computed-but-not-durable window), resume in a fresh process, and the
    stitched SweepResult equals an uninterrupted run bit for bit."""
    ck = str(tmp_path / "ck")
    args = ["--ckpt-dir", ck, "--backend", backend]
    r = _run_cli(tmp_path, tmp_path / "dead.npz", args,
                 FaultPlan(kill_at_unit=2))
    assert r.returncode == -9, (r.returncode, r.stderr)
    assert not (tmp_path / "dead.npz").exists()

    rep_out = tmp_path / "rep.json"
    r = _run_cli(tmp_path, tmp_path / "resumed.npz",
                 args + ["--report-out", str(rep_out)])
    assert r.returncode == 0, r.stderr
    rep = json.loads(Path(rep_out).read_text())
    assert rep["units_resumed"] == 2 and rep["units_run"] == 1

    r = _run_cli(tmp_path, tmp_path / "solo.npz",
                 ["--backend", backend])
    assert r.returncode == 0, r.stderr
    a = np.load(tmp_path / "resumed.npz")
    b = np.load(tmp_path / "solo.npz")
    for f in a.files:
        np.testing.assert_array_equal(a[f], b[f], err_msg=f)


def test_mesh_runner_replans_to_smaller_mesh_midcampaign():
    """8 forced host devices: a sharded campaign loses half its workers
    mid-sweep, the elastic re-plan rebuilds a 4-device mesh from the
    survivors, and the remaining units complete with unchanged discrete
    results (subprocess: the device-count flag must be set pre-jax)."""
    import textwrap
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np
        from repro.apps import mibench
        from repro.core.characterization import default_profile
        from repro.core.hwconfig import TOPOLOGIES
        from repro.runtime.faults import FaultInjector, FaultPlan
        from repro.service import FleetMonitor, ResumableSweepRunner

        ks = [mibench.bitcnt(n_words=16), mibench.crc32(n_words=3)]
        hws = [mk() for mk in TOPOLOGIES.values()]            # H=5
        mems = np.stack([k.mem_init for k in ks])             # D=2
        kw = dict(programs=[k.program for k in ks],
                  profile=default_profile(), hw_configs=hws,
                  mem_images=mems, unit_size=8, max_steps=256)

        ref, _ = ResumableSweepRunner(**kw).run()             # B=20, 3 units

        mesh = jax.make_mesh((8,), ("data",))
        t = {"now": 0.0}
        mon = FleetMonitor([f"dev{i}" for i in range(8)],
                           clock=lambda: t["now"], timeout=5.0)
        dead = tuple((1, f"dev{i}") for i in range(4, 8))
        inj = FaultInjector(FaultPlan(dead_nodes=dead))
        r = ResumableSweepRunner(mesh=mesh, monitor=mon, injector=inj,
                                 **kw)
        for k_ in r.pending_units():
            r.run_unit(k_)
            t["now"] += 6.0
        assert len(r.report.replans) == 1, r.report.replans
        ev = r.report.replans[0]
        assert sorted(ev["dropped"]) == sorted(n for _, n in dead)
        assert ev["elastic_plan"]["n_devices"] == 4
        assert r.mesh.devices.size == 4
        res = r.stitch()
        for f in ("latency_cc", "checksum", "steps_executed"):
            np.testing.assert_array_equal(
                np.asarray(getattr(ref, f)), np.asarray(getattr(res, f)))
        print("MESH_REPLAN_OK")
    """)
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, cwd="/root/repo",
                       env=dict(os.environ, PYTHONPATH="src"),
                       timeout=1200)
    assert "MESH_REPLAN_OK" in r.stdout, (r.stdout[-1500:],
                                          r.stderr[-1500:])


# ---------------------------------------------------------------------------
# Sweep service: packing, backpressure, deadlines, streaming
# ---------------------------------------------------------------------------

def _requests(grid):
    ks = [mibench.bitcnt(n_words=16), mibench.crc32(n_words=3)]
    hws = grid["hw_configs"]
    mems = grid["mem_images"]
    return (SweepRequest(programs=[ks[0].program], hw_configs=hws,
                         mem_images=mems[:1]),
            SweepRequest(programs=[ks[1].program], hw_configs=hws,
                         mem_images=mems[1:]))


def test_service_packs_requests_and_matches_solo(grid, profile):
    """Two requests packed into one merged campaign each get exactly the
    result of a solo dse.sweep over their own sub-grid."""
    r1, r2 = _requests(grid)
    svc = SweepService(profile, slots=1, unit_size=2, max_steps=MAX_STEPS)
    svc.submit(r1)
    svc.submit(r2)
    out = svc.drain()
    assert set(out) == {r1.rid, r2.rid}
    for req in (r1, r2):
        solo = dse.sweep(program=list(req.programs)[0], profile=profile,
                         hw_configs=req.hw_configs,
                         mem_images=req.mem_images, max_steps=MAX_STEPS)
        got = out[req.rid]
        assert not got.expired and got.skipped_lanes == 0
        for f in DISCRETE:
            np.testing.assert_array_equal(
                np.asarray(getattr(solo, f)), got.arrays[f], err_msg=f)
        for f in ("energy_pj", "power_mw"):
            np.testing.assert_allclose(
                np.asarray(getattr(solo, f)), got.arrays[f], rtol=1e-6,
                err_msg=f)


def test_service_streams_partials(grid, profile):
    """Every completed unit is pushed to its owners in request-local
    lane coordinates; unit_size=1 means one partial per lane."""
    parts = []
    r1, r2 = _requests(grid)        # 2 lanes each (1 prog x 2 hw x 1 img)
    r1.on_partial = lambda rid, lo, hi, p: parts.append((rid, lo, hi))
    svc = SweepService(profile, slots=1, unit_size=1, max_steps=MAX_STEPS)
    svc.submit(r1)
    svc.submit(r2)
    out = svc.drain()
    assert parts == [(r1.rid, 0, 1), (r1.rid, 1, 2)]
    assert set(out) == {r1.rid, r2.rid}


def test_service_backpressure(grid, profile):
    r1, r2 = _requests(grid)
    svc = SweepService(profile, slots=1, queue_max=1, unit_size=2,
                       max_steps=MAX_STEPS)
    svc.submit(r1)
    with pytest.raises(ServiceOverloaded):
        svc.submit(r2)


def test_service_deadline_skips_only_expired_request(grid, profile):
    """An expired request's remaining units are skipped (zero-stitched,
    flagged); its co-tenant still gets full exact results."""
    t = {"now": 0.0}
    r1, r2 = _requests(grid)
    # widen r1 to 4 lanes (1 prog x 2 hw x 2 images) = two units
    r1.mem_images = grid["mem_images"]
    r1.deadline_s = 0.5               # expires before its second unit
    svc = SweepService(profile, slots=1, unit_size=2, max_steps=MAX_STEPS,
                       clock=lambda: t["now"])
    svc.submit(r1)
    svc.submit(r2)
    svc.step()                        # runs r1's first unit
    t["now"] = 1.0                    # r1 now past deadline
    out = svc.drain()
    got1, got2 = out[r1.rid], out[r2.rid]
    assert got1.expired and got1.skipped_lanes == 2
    assert np.all(got1.arrays["latency_cc"][2:] == 0)      # skipped lanes
    assert np.any(got1.arrays["latency_cc"][:2] != 0)      # delivered unit
    solo = dse.sweep(program=list(r2.programs)[0], profile=profile,
                     hw_configs=r2.hw_configs, mem_images=r2.mem_images,
                     max_steps=MAX_STEPS)
    assert not got2.expired
    np.testing.assert_array_equal(np.asarray(solo.latency_cc),
                                  got2.arrays["latency_cc"])

"""On-device top-k / Pareto-front reduction (analysis.pareto).

The tentpole contract: a sweep carrying ``reduce=`` ships only the
``O(G*K)`` per-program candidate sets to the host, and those candidates
are *bit-identical* to the numpy oracle applied to the full ``(B,)``
result arrays -- on both backends, across bucketed packing, work-unit
partitioning (checkpoint/resume included), and a forced 8-host-device
mesh.  Merges are associative, padding/tie/duplicate lanes are handled
by construction, and the sweep service streams per-unit fronts that
fold to exactly the monolithic answer.
"""
import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest

from repro.analysis.pareto import (CANDIDATE_FIELDS, REDUCED_FIELDS,
                                   ParetoFront, ReducedResult, TopK,
                                   merge_reduced, reduce_on_device,
                                   reduce_oracle, reduced_nbytes,
                                   spec_from_str, spec_to_str)
from repro.apps import mibench
from repro.core import dse
from repro.core.hwconfig import TOPOLOGIES
from repro.core.isa import asm
from repro.core.program import ProgramBuilder, bucket_programs
from repro.service import (CheckpointMismatch, ResumableSweepRunner,
                           SweepRequest, SweepService)

MAX_STEPS = 256          # one compiled shape shared with the service tests

SPECS = [TopK("energy_pj", k=3), TopK("edp", k=4),
         ParetoFront(axes=("latency_cc", "energy_pj"), max_points=8),
         ParetoFront(axes=("energy_pj", "power_mw"), max_points=5)]


def _rand_fields(rng, B):
    """Sweep-result quintet with heavy ties and duplicate points."""
    return (rng.integers(1, 12, B).astype(np.int32),          # latency_cc
            (rng.integers(1, 10, B) * 0.5).astype(np.float32),  # energy_pj
            (rng.integers(1, 6, B) * 0.25).astype(np.float32),  # power_mw
            rng.integers(-5, 5, B).astype(np.int32),          # checksum
            rng.integers(1, 99, B).astype(np.int32))          # steps


def _assert_reduced_equal(a, b, msg=""):
    for f in REDUCED_FIELDS:
        np.testing.assert_array_equal(np.asarray(getattr(a, f)),
                                      np.asarray(getattr(b, f)),
                                      err_msg=f"{msg}{f}")


@pytest.fixture(scope="module")
def grid(profile):
    ks = [mibench.bitcnt(n_words=16), mibench.crc32(n_words=3)]
    hws = [TOPOLOGIES["baseline"](), TOPOLOGIES["c_interleaved"]()]
    mems = np.stack([k.mem_init for k in ks])
    return dict(programs=[k.program for k in ks], profile=profile,
                hw_configs=hws, mem_images=mems, max_steps=MAX_STEPS)


def _oracle_of_sweep(spec, grid, res):
    """The reference answer: numpy oracle over the full unreduced grid."""
    G = len(grid["programs"])
    H, D = len(grid["hw_configs"]), grid["mem_images"].shape[0]
    fields = tuple(np.asarray(getattr(res, f)) for f in res._fields)
    return reduce_oracle(spec, fields, np.repeat(np.arange(G), H * D),
                         np.arange(G * H * D), G)


# ---------------------------------------------------------------------------
# Spec mechanics
# ---------------------------------------------------------------------------

def test_spec_validation_and_roundtrip():
    with pytest.raises(ValueError, match="objective"):
        TopK("watts", 3)
    with pytest.raises(ValueError, match="k must"):
        TopK("edp", 0)
    with pytest.raises(ValueError, match="distinct"):
        ParetoFront(axes=("edp", "edp"))
    with pytest.raises(ValueError, match="axis"):
        ParetoFront(axes=("latency_cc", "joules"))
    with pytest.raises(ValueError, match="unknown reduction"):
        spec_from_str("median:edp:3")
    for spec in SPECS:
        assert spec_from_str(spec_to_str(spec)) == spec


def test_reduced_nbytes_is_o_gk_not_b():
    """The transfer contract: bytes depend on (G, K) only."""
    spec = TopK("edp", k=8)
    n = reduced_nbytes(4, spec)
    assert n == 4 * (8 * 4 * len(CANDIDATE_FIELDS) + 2 * 4)
    # kilobytes for a million-point grid's worth of programs
    assert reduced_nbytes(4, spec) < 10_000


# ---------------------------------------------------------------------------
# Device reducer == numpy oracle (padding / ties / duplicates)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", SPECS, ids=spec_to_str)
def test_device_reducer_matches_oracle(spec):
    """Randomized parity with ~20% masked pad lanes, tied keys and
    duplicate points (the `<=`-dominance and index-tiebreak edge cases),
    plus segments with zero candidates."""
    rng = np.random.default_rng(7)
    for trial in range(4):
        B, G = int(rng.integers(6, 70)), int(rng.integers(2, 5))
        fields = _rand_fields(rng, B)
        prog = rng.integers(0, G, B).astype(np.int32)
        prog[prog == G - 1] = 0              # one empty segment sometimes
        lane = np.arange(B, dtype=np.int32)
        lane[rng.random(B) < 0.2] = -1       # masked pad lanes
        want = reduce_oracle(spec, fields, prog, lane, G)
        got = reduce_on_device(spec, fields, prog, lane, G)
        _assert_reduced_equal(want, got, msg=f"trial {trial}: ")


def test_duplicate_front_points_both_kept():
    """Exact duplicates of a Pareto point are not dominated (strict-on-
    one-axis rule) -- both stay, ordered by ascending lane index."""
    spec = ParetoFront(axes=("latency_cc", "energy_pj"), max_points=8)
    lat = np.array([5, 5, 9], np.int32)
    en = np.array([2.0, 2.0, 1.0], np.float32)
    pw = np.zeros(3, np.float32)
    ck = st = np.zeros(3, np.int32)
    fields = (lat, en, pw, ck, st)
    prog = np.zeros(3, np.int32)
    lane = np.arange(3, dtype=np.int32)
    want = reduce_oracle(spec, fields, prog, lane, 1)
    got = reduce_on_device(spec, fields, prog, lane, 1)
    _assert_reduced_equal(want, got)
    assert int(got.count[0]) == 3
    np.testing.assert_array_equal(got.indices[0, :3], [0, 1, 2])


# ---------------------------------------------------------------------------
# Merge: associative, idempotent, clip-aware
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("spec", SPECS, ids=spec_to_str)
def test_merge_is_associative_and_matches_monolithic(spec):
    rng = np.random.default_rng(11)
    B, G = 60, 3
    fields = _rand_fields(rng, B)
    prog = rng.integers(0, G, B).astype(np.int32)
    lane = np.arange(B, dtype=np.int32)
    mono = reduce_oracle(spec, fields, prog, lane, G)
    if isinstance(spec, ParetoFront) and int(mono.clipped.sum()):
        pytest.skip("clipped front: merge exactness not guaranteed")
    cuts = [0, 20, 45, B]
    parts = []
    for lo, hi in zip(cuts, cuts[1:]):
        parts.append(reduce_oracle(
            spec, tuple(f[lo:hi] for f in fields), prog[lo:hi],
            lane[lo:hi], G))
    left = merge_reduced(spec, [merge_reduced(spec, parts[:2]), parts[2]])
    right = merge_reduced(spec, [parts[0], merge_reduced(spec, parts[1:])])
    flat = merge_reduced(spec, parts)
    for m, nm in ((left, "left"), (right, "right"), (flat, "flat")):
        _assert_reduced_equal(mono, m, msg=f"{nm}: ")
    # idempotent: re-delivering the same part changes nothing
    _assert_reduced_equal(mono, merge_reduced(spec, parts + [parts[1]]),
                          msg="idempotent: ")


def test_merge_carries_clipped_counts():
    """A part that overflowed max_points flags the merge as inexact."""
    spec = ParetoFront(axes=("latency_cc", "energy_pj"), max_points=2)
    lat = np.array([1, 2, 3], np.int32)
    en = np.array([3.0, 2.0, 1.0], np.float32)   # 3-point front, K=2
    fields = (lat, en, np.zeros(3, np.float32),
              np.zeros(3, np.int32), np.zeros(3, np.int32))
    part = reduce_oracle(spec, fields, np.zeros(3, np.int32),
                         np.arange(3, dtype=np.int32), 1)
    assert int(part.clipped[0]) == 1
    merged = merge_reduced(spec, [part, part])
    assert int(merged.clipped[0]) >= 1


# ---------------------------------------------------------------------------
# dse.sweep(reduce=): both backends, bucketed packing, trip-count buckets
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["xla", "pallas"])
@pytest.mark.parametrize("max_buckets", [1, 3])
def test_sweep_reduce_matches_oracle(grid, backend, max_buckets):
    kw = dict(grid, backend=backend, max_buckets=max_buckets,
              interpret=True if backend == "pallas" else None)
    full = dse.sweep(**kw)
    for spec in (TopK("edp", k=3),
                 ParetoFront(axes=("latency_cc", "energy_pj"),
                             max_points=8)):
        got = dse.sweep(**kw, reduce=spec)
        _assert_reduced_equal(_oracle_of_sweep(spec, grid, full), got,
                              msg=f"{spec_to_str(spec)}: ")


def test_sweep_reduce_with_observed_steps_buckets(grid):
    """Trip-count bucketing composes with reduction: the re-bucketed
    sweep still merges to the canonical answer."""
    spec = TopK("energy_pj", k=3)
    full = dse.sweep(**grid)
    got = dse.sweep(**grid, max_buckets=2, observed_steps=[40, 6],
                    reduce=spec)
    _assert_reduced_equal(_oracle_of_sweep(spec, grid, full), got)


def test_bucketed_fn_reduce_matches_sweep(grid):
    spec = ParetoFront(axes=("latency_cc", "energy_pj"), max_points=8)
    fn = dse.make_bucketed_sweep_fn(
        grid["programs"], grid["profile"], grid["hw_configs"],
        grid["mem_images"], max_steps=MAX_STEPS, max_buckets=2,
        reduce=spec)
    assert fn.reduce == spec
    want = dse.sweep(**grid, max_buckets=2, reduce=spec)
    _assert_reduced_equal(want, fn())
    _assert_reduced_equal(want, fn())        # held plan: stable across calls


# ---------------------------------------------------------------------------
# Work-unit partitioning (runner): per-unit fronts, checkpoints, resume
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("unit_size", [1, 3, 8])
def test_runner_unit_merge_matches_unpartitioned(grid, unit_size):
    """Any unit partition's merged fronts equal the oracle over the same
    runner's unreduced stitch (same executables, same float values)."""
    spec = TopK("edp", k=3)
    kw = dict(programs=grid["programs"], profile=grid["profile"],
              hw_configs=grid["hw_configs"], mem_images=grid["mem_images"],
              unit_size=unit_size, max_steps=MAX_STEPS)
    full, _ = ResumableSweepRunner(**kw).run()
    red, _ = ResumableSweepRunner(**kw, reduce=spec).run()
    _assert_reduced_equal(_oracle_of_sweep(spec, grid, full), red)


def test_runner_checkpoints_store_compacted_fronts(grid, tmp_path):
    """A reduced unit's checkpoint is the (G, K) candidate set -- not the
    lane slice -- and a fresh process merges resumed + new units to the
    bit-identical campaign answer."""
    spec = ParetoFront(axes=("latency_cc", "energy_pj"), max_points=8)
    G = len(grid["programs"])
    kw = dict(programs=grid["programs"], profile=grid["profile"],
              hw_configs=grid["hw_configs"], mem_images=grid["mem_images"],
              unit_size=3, max_steps=MAX_STEPS, reduce=spec)
    solo, _ = ResumableSweepRunner(**kw).run()

    ck = str(tmp_path / "ck")
    pre = ResumableSweepRunner(ckpt_dir=ck, **kw)
    _, res_np = pre.run_unit(0)
    assert res_np["indices"].shape == (G, spec.max_points)
    pre.run_unit(1)
    pre.mgr.wait()

    resumed = ResumableSweepRunner(ckpt_dir=ck, **kw)
    got, rep = resumed.run()
    assert rep.units_resumed == 2
    _assert_reduced_equal(solo, got)


def test_runner_reduce_spec_is_part_of_fingerprint(grid, tmp_path):
    """A checkpoint directory cannot mix reduced and differently-reduced
    (or unreduced) campaigns."""
    ck = str(tmp_path / "ck")
    kw = dict(programs=grid["programs"], profile=grid["profile"],
              hw_configs=grid["hw_configs"], mem_images=grid["mem_images"],
              unit_size=3, max_steps=MAX_STEPS)
    pre = ResumableSweepRunner(ckpt_dir=ck, **kw, reduce=TopK("edp", k=3))
    pre.run_unit(0)
    pre.mgr.wait()
    with pytest.raises(CheckpointMismatch):
        ResumableSweepRunner(ckpt_dir=ck, **kw, reduce=TopK("edp", k=4))
    with pytest.raises(CheckpointMismatch):
        ResumableSweepRunner(ckpt_dir=ck, **kw)


def test_sigkill_reduced_campaign_resumes_bit_identical(tmp_path):
    """The acceptance drill: SIGKILL a reduced campaign pre-commit,
    resume in a fresh process, and the merged fronts equal an
    uninterrupted run's exactly."""
    from repro.runtime.faults import FAULT_PLAN_ENV, FaultPlan

    def run_cli(out, extra, fault_plan=None):
        env = dict(os.environ, PYTHONPATH="src")
        if fault_plan is not None:
            env[FAULT_PLAN_ENV] = fault_plan.to_json()
        return subprocess.run(
            [sys.executable, "-m", "repro.service",
             "--kernels", "bitcnt,crc32", "--unit-size", "3",
             "--max-steps", str(MAX_STEPS),
             "--reduce", "pareto:latency_cc,energy_pj:8",
             "--out", str(out), *extra],
            env=env, cwd=str(Path(__file__).resolve().parents[1]),
            capture_output=True, text=True)

    ck = str(tmp_path / "ck")
    r = run_cli(tmp_path / "dead.npz", ["--ckpt-dir", ck],
                FaultPlan(kill_at_unit=2))
    assert r.returncode == -9, (r.returncode, r.stderr)

    rep_out = tmp_path / "rep.json"
    r = run_cli(tmp_path / "resumed.npz",
                ["--ckpt-dir", ck, "--report-out", str(rep_out)])
    assert r.returncode == 0, r.stderr
    rep = json.loads(rep_out.read_text())
    assert rep["units_resumed"] == 2 and rep["units_run"] >= 1

    r = run_cli(tmp_path / "solo.npz", [])
    assert r.returncode == 0, r.stderr
    a, b = np.load(tmp_path / "resumed.npz"), np.load(tmp_path / "solo.npz")
    assert set(a.files) == set(REDUCED_FIELDS)
    for f in a.files:
        np.testing.assert_array_equal(a[f], b[f], err_msg=f)


# ---------------------------------------------------------------------------
# Mesh: per-device reduction + gathered-candidate merge == unsharded
# ---------------------------------------------------------------------------

def test_mesh_reduced_parity_8_devices(grid):
    """8 forced host devices (subprocess -- the flag must be set before
    jax imports): sweep(mesh=..., reduce=...) reduces per device and
    merges the gathered n_devices*K candidates to the unsharded answer,
    on both backends, with non-divisible-grid padding (B=12 pads to 16).
    Candidate *selection* (indices, counts, discrete fields) is exact;
    the float32 energy/power accumulators of the very same lanes may
    differ by an ULP across the different compiled batch shapes, so
    those follow the repo's rtol=1e-6 cross-shape convention."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np
        from repro.analysis.pareto import (REDUCED_FIELDS, ParetoFront,
                                           TopK, spec_to_str)
        from repro.apps import mibench
        from repro.core import dse
        from repro.core.characterization import default_profile
        from repro.core.hwconfig import TOPOLOGIES

        ks = [mibench.bitcnt(n_words=16), mibench.crc32(n_words=3)]
        hws = [TOPOLOGIES["baseline"](), TOPOLOGIES["c_interleaved"](),
               TOPOLOGIES["d_dma_per_pe"]()]
        mems = np.stack([k.mem_init for k in ks])
        kw = dict(programs=[k.program for k in ks],
                  profile=default_profile(), hw_configs=hws,
                  mem_images=mems, max_steps=256)       # B=12: pad to 16
        mesh = jax.make_mesh((8,), ("data",))
        for spec in (TopK("edp", k=3),
                     ParetoFront(axes=("latency_cc", "energy_pj"),
                                 max_points=8)):
            for backend in ("xla", "pallas"):
                ref = dse.sweep(**kw, backend=backend, reduce=spec)
                got = dse.sweep(**kw, backend=backend, mesh=mesh,
                                reduce=spec)
                for f in REDUCED_FIELDS:
                    a = np.asarray(getattr(ref, f))
                    b = np.asarray(getattr(got, f))
                    tag = f"{spec_to_str(spec)} {backend} {f}"
                    if f in ("energy_pj", "power_mw"):
                        np.testing.assert_allclose(a, b, rtol=1e-6,
                                                   err_msg=tag)
                    else:
                        np.testing.assert_array_equal(a, b, err_msg=tag)
        print("MESH_REDUCED_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", prog], env=env,
                       cwd=str(Path(__file__).resolve().parents[1]),
                       capture_output=True, text=True)
    assert "MESH_REDUCED_OK" in r.stdout, (r.stdout[-1500:],
                                           r.stderr[-1500:])


# ---------------------------------------------------------------------------
# Service: streamed per-unit fronts fold to the monolithic answer
# ---------------------------------------------------------------------------

def test_service_streamed_fronts_merge_to_monolithic(grid, profile):
    """Each reduced request's streamed partials (per-unit fronts in
    request-local coordinates) merge with ``merge_reduced`` to exactly
    the final RequestResult, which equals a solo reduced sweep."""
    spec = TopK("energy_pj", k=3)
    ks = [mibench.bitcnt(n_words=16), mibench.crc32(n_words=3)]
    parts = {}
    reqs = []
    for k in ks:
        r = SweepRequest(programs=[k.program],
                         hw_configs=grid["hw_configs"],
                         mem_images=grid["mem_images"], reduce=spec)
        r.on_partial = lambda rid, lo, hi, p: parts.setdefault(
            rid, []).append(p)
        reqs.append(r)
    svc = SweepService(profile, slots=1, unit_size=3, max_steps=MAX_STEPS)
    for r in reqs:
        svc.submit(r)
    out = svc.drain()
    for r in reqs:
        got = out[r.rid]
        assert not got.expired
        streamed = merge_reduced(spec, [
            ReducedResult(**{f: p[f] for f in REDUCED_FIELDS})
            for p in parts[r.rid]])
        final = ReducedResult(**{f: got.arrays[f] for f in REDUCED_FIELDS})
        _assert_reduced_equal(final, streamed, msg="streamed vs final: ")
        solo = dse.sweep(programs=list(r.programs), profile=profile,
                         hw_configs=r.hw_configs, mem_images=r.mem_images,
                         max_steps=MAX_STEPS, reduce=spec)
        np.testing.assert_array_equal(solo.indices, final.indices)
        np.testing.assert_array_equal(solo.count, final.count)
        np.testing.assert_array_equal(solo.latency_cc, final.latency_cc)


def test_service_packs_only_same_reduce_requests(grid, profile):
    """A reduced and an unreduced request never share a slot (one merged
    campaign runs one fused reduction); both still get exact answers."""
    spec = TopK("energy_pj", k=3)
    ks = [mibench.bitcnt(n_words=16), mibench.crc32(n_words=3)]
    r_red = SweepRequest(programs=[ks[0].program],
                         hw_configs=grid["hw_configs"],
                         mem_images=grid["mem_images"], reduce=spec)
    r_full = SweepRequest(programs=[ks[1].program],
                          hw_configs=grid["hw_configs"],
                          mem_images=grid["mem_images"])
    svc = SweepService(profile, slots=2, unit_size=3, max_steps=MAX_STEPS)
    svc.submit(r_red)
    svc.submit(r_full)
    out = svc.drain()
    assert all(len(rec["rids"]) == 1 for rec in svc.admission_log)
    assert set(out[r_red.rid].arrays) == set(REDUCED_FIELDS)
    solo = dse.sweep(programs=list(r_full.programs), profile=profile,
                     hw_configs=r_full.hw_configs,
                     mem_images=r_full.mem_images, max_steps=MAX_STEPS)
    np.testing.assert_array_equal(np.asarray(solo.latency_cc),
                                  out[r_full.rid].arrays["latency_cc"])


# ---------------------------------------------------------------------------
# Trip-count-aware bucketing (bucket_programs(observed_steps=...))
# ---------------------------------------------------------------------------

def _loop_program(iters, name):
    """Fixed instruction count, data-dependent-looking trip count."""
    pb = ProgramBuilder(16, name)
    pb.instr({0: asm("MV", "R1", "IMM", imm=iters)})
    top = pb.instr({0: asm("SADD", "R0", "R0", "IMM", imm=1)})
    pb.instr({0: asm("BLT", a="R0", b="R1", imm=top)})
    pb.exit()
    return pb.build()


def test_observed_steps_buckets_beat_static_length():
    """Equal-length kernels with divergent trip counts: static length
    sees one class (everything convoys behind the slowest), observed
    steps split fast from slow -- strictly lower total padded step
    cost (the regression the satellite guards)."""
    progs = [_loop_program(2, "fast_a"), _loop_program(40, "slow_a"),
             _loop_program(3, "fast_b"), _loop_program(38, "slow_b")]
    obs = [8, 160, 12, 152]               # steps_executed from a prior run
    static = bucket_programs(progs, 2)
    assert static.n_buckets == 1          # lengths are identical
    by_steps = bucket_programs(progs, 2, observed_steps=obs)
    assert by_steps.n_buckets == 2
    assert sorted(map(sorted, by_steps.groups)) == [[0, 2], [1, 3]]

    def convoy_cost(buckets):
        return sum(len(g) * max(obs[i] for i in g) for g in buckets.groups)

    assert convoy_cost(by_steps) < convoy_cost(static)


def test_observed_steps_length_mismatch_raises():
    with pytest.raises(ValueError, match="observed_steps"):
        bucket_programs([_loop_program(2, "a")], 2, observed_steps=[1, 2])


def test_service_buckets_by_observed_steps_history(profile):
    """The service's per-kernel history drives admission: after a first
    campaign records how long each kernel RAN, a window of equal-length
    requests is bucketed by observed steps -- fast and slow kernels no
    longer share a convoy."""
    fast, slow = _loop_program(2, "hist_fast"), _loop_program(35, "hist_slow")
    assert fast.n_instrs == slow.n_instrs
    mems = np.zeros((1, 256), np.int32)
    hws = [TOPOLOGIES["baseline"]()]

    def req(p):
        return SweepRequest(programs=[p], hw_configs=hws, mem_images=mems)

    svc = SweepService(profile, slots=2, unit_size=2, max_steps=MAX_STEPS,
                       mem_size=256)
    svc.submit(req(fast))
    svc.submit(req(slow))
    svc.drain()
    assert svc.admission_log[0]["bucket_by"] == "length"
    assert svc.steps_history["hist_slow"] > svc.steps_history["hist_fast"]

    r1, r2, r3, r4 = req(fast), req(slow), req(fast), req(slow)
    for r in (r1, r2, r3, r4):
        svc.submit(r)
    svc.drain()
    by_steps = [rec for rec in list(svc.admission_log)[1:]
                if rec["bucket_by"] == "observed_steps"]
    assert by_steps, svc.admission_log
    # the first observed-steps slot packs the two fast requests together
    # and leaves the slow ones for their own slot
    assert sorted(by_steps[0]["rids"]) == sorted([r1.rid, r3.rid])

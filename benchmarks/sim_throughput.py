"""The "instantaneous result" claim (paper Section 1): design points per
second through the fused simulate+estimate sweep.

Eight comparisons, all machine-readable in BENCH_sim_throughput.json so
the perf trajectory is trackable across PRs (schema: bench_schema.json,
validated in CI by benchmarks.validate_bench):
  * single-point trace path vs the batched fused path (the paper's win);
  * sweep backends: XLA scan vs the fused multi-step Pallas engine
    (kernels/cgra_sweep) across batch sizes.  Off-TPU the Pallas engine
    runs in interpret mode -- a correctness proxy, not its speed; the
    JSON records which mode ran;
  * multi-kernel lane (one row per grid scale, G=3 and G=8): G different
    kernels swept through the bucketed packed path (``dse.sweep`` --
    length buckets, one lru-cached executable per bucket, eager
    steady-state calls) vs the per-program loop (G compiles), with
    compile seconds, per-bucket shapes, trace counts and the
    ``steady_ratio`` (packed/loop steady throughput -- the CI
    regression gate's key metric, >= 1 means packed wins) all recorded;
  * on-device reduction lane: the bucketed packed sweep with and
    without a ``reduce=`` spec (top-k / Pareto front computed inside
    the compiled sweep) -- device->host result bytes drop from O(B) to
    O(G*K) while steady throughput stays within noise, and the device
    candidates are re-checked bit-identical to the numpy oracle;
  * mapping-search lane: seeded candidate enumeration throughput
    (candidates/sec incl. oracle verification), the best-vs-worst
    candidate EDP spread (why mapping search pays), and the packed
    (K mappings x H x D) sweep vs K per-candidate loops
    (``batched_vs_loop``, CI-gated) with packed trace counts;
  * the estimator's memory-contention scheduler: seed S x P Python loop
    vs the vectorized O(P) scheduler (must be >= 10x on 2048 x 16);
  * the crash-safe sweep service (service/runner): per-unit checkpoint
    overhead vs the plain partitioned run, and cold recovery time after
    a mid-campaign kill vs re-running from scratch (docs/robustness.md);
  * transport lane: the identical campaign driven through the
    in-process ``SweepService`` vs over the loopback HTTP front end
    (``SweepClient`` -> ``SweepTransport``: JSON+base64 submission,
    ndjson per-unit record streaming, cursor acks, idempotent folding)
    -- ``overhead_ratio`` (transport/in-process steady seconds, CI
    ceiling-gated) plus ``requests_per_s`` for the fixed per-request
    HTTP cost, with the folded transport arrays re-checked against the
    in-process result on every run (docs/service.md).

Steps/sec is *true* steps: ``SweepResult.steps_executed`` counts the
instructions each design point actually ran (early-exiting kernels stop
well short of ``max_steps``), so the JSON reports what the engine did,
not the nominal budget.  Both are recorded per row.

``BENCH_SMOKE=1`` (or ``--smoke``) shrinks every dimension -- tiny
kernel, one small batch, short contention trace -- for the CI
benchmark-smoke lane: same code paths, same JSON shape, seconds not
minutes.  Smoke mode writes ``BENCH_sim_throughput.smoke.json``
(gitignored) so the tracked perf history is never overwritten with
non-comparable numbers.
"""
from __future__ import annotations

import json
import os
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from repro.apps import mibench
from repro.core import dse, estimate
from repro.core.characterization import default_profile
from repro.core.estimator import mem_completion_np, mem_completion_np_loop
from repro.core.hwconfig import TOPOLOGIES, HwConfig, stack_configs

from .common import Report, timeit

SMOKE = (os.environ.get("BENCH_SMOKE", "") not in ("", "0")
         or "--smoke" in sys.argv[1:])
# Smoke numbers are not comparable to real runs; keep them out of the
# tracked perf-history file (gitignored .smoke.json instead).
JSON_PATH = Path(__file__).resolve().parents[1] / (
    "BENCH_sim_throughput.smoke.json" if SMOKE
    else "BENCH_sim_throughput.json")
BATCH_SIZES = (4,) if SMOKE else (8, 64)
MEM_BENCH_STEPS = 128 if SMOKE else 2048


def _kernel():
    return mibench.bitcnt(n_words=16) if SMOKE else mibench.sha_mix()


def _bench_backends(rep: Report, rows: list) -> None:
    prof = default_profile()
    k = _kernel()
    hws = [mk() for mk in TOPOLOGIES.values()]

    def single():
        final, trace = k.run()
        estimate(k.program, trace, prof, TOPOLOGIES["baseline"](), "vi")
        return trace

    def record(row: dict) -> None:
        rows.append(row)
        rep.add(**{k_: v for k_, v in row.items() if k_ != "backend"})

    # the warmup run doubles as the step-count probe (no extra execution)
    steps_single = int(np.asarray(single().valid).sum())
    t_single = timeit(single, repeats=3, warmup=0)
    record(dict(path="single_trace", backend="trace", B=1,
                seconds_per_batch=t_single, points_per_s=1.0 / t_single,
                steps_per_s=steps_single / t_single,
                steps_executed=steps_single, steps_nominal=k.max_steps,
                speedup_vs_single=1.0))

    interpret = jax.default_backend() != "tpu"
    for B in BATCH_SIZES:
        mems = jnp.asarray(
            np.broadcast_to(k.mem_init, (B, k.mem_init.size)).copy())
        hw_b = stack_configs([hws[i % len(hws)] for i in range(B)])
        for backend in ("xla", "pallas"):
            fn = jax.jit(dse.make_sweep_fn(
                k.program, prof, max_steps=k.max_steps, backend=backend))

            def run_batch():
                jax.block_until_ready(fn(mems, hw_b))

            # compile+warm once and read the true executed instructions
            # (summed over the batch -- what steps/sec means for an
            # early-exiting sweep) off that same run
            res = jax.block_until_ready(fn(mems, hw_b))
            steps_true = int(np.asarray(res.steps_executed).sum())
            t = timeit(run_batch, repeats=3, warmup=0)
            label = backend + ("_interpret" if backend == "pallas"
                               and interpret else "")
            record(dict(path=f"{label}_batch_{B}", backend=label, B=B,
                        seconds_per_batch=t, points_per_s=B / t,
                        steps_per_s=steps_true / t,
                        steps_executed=steps_true,
                        steps_nominal=B * k.max_steps,
                        speedup_vs_single=(t_single * B) / t))


def _multi_kernels():
    if SMOKE:
        return [mibench.bitcnt(n_words=16), mibench.crc32(n_words=3)]
    return [mibench.bitcnt(), mibench.crc32(), mibench.susan_thresh()]


def _multi_kernels_g8():
    """Eight kernel instances over four program-length classes -- the
    packed-grid regime bucketing is built for.  Each length class is a
    duplicated submission (the multi-tenant service case: two clients
    sweeping the same kernel), so padded length predicts runtime inside
    every bucket and the bucketed packed path carries no convoy waste;
    heterogeneous-runtime length classes are the workload the docs'
    padding-waste math bounds (docs/performance.md)."""
    if SMOKE:
        return [mibench.bitcnt(n_words=16), mibench.bitcnt(n_words=16),
                mibench.crc32(n_words=3), mibench.crc32(n_words=3),
                mibench.susan_thresh(n_pixels=16),
                mibench.susan_thresh(n_pixels=16),
                mibench.sha_mix(rounds=6), mibench.sha_mix(rounds=6)]
    return [mibench.bitcnt(n_words=64), mibench.bitcnt(n_words=64),
            mibench.crc32(n_words=6), mibench.crc32(n_words=6),
            mibench.susan_thresh(n_pixels=128),
            mibench.susan_thresh(n_pixels=128),
            mibench.sha_mix(rounds=24), mibench.sha_mix(rounds=24)]


def _first_and_steady(run):
    """(first-call seconds, steady-state median seconds): the first call
    pays trace+compile, so their difference is the compile cost."""
    import time as _time
    t0 = _time.perf_counter()
    run()
    first = _time.perf_counter() - t0
    steady = timeit(run, repeats=3, warmup=0)
    return first, steady


def _bench_multi_kernel_one(rep: Report, ks: list) -> dict:
    """G kernel instances: the bucketed packed plan (length buckets, one
    lru-cached operand executable per bucket, per-bucket autotuned
    chunk/blk knobs, held across calls via ``dse.make_bucketed_sweep_fn``
    -- the service steady state) vs the per-program python loop at the
    engine defaults (one constant-closure compile per kernel).  XLA
    backend -- the compile-amortization story is backend-independent and
    the interpret-mode Pallas numbers would only measure the interpreter.

    Both sides run the identical G x H x D grid (every kernel against
    every image), so steady_ratio = loop/packed steady seconds is a
    same-machine, same-work ratio -- the noise-robust number the CI
    regression gate keys on.  Before timing, each bucket's shape class
    is autotuned over a compact candidate grid (``tune_sweep``) into the
    bench-local cache set up by ``run()`` -- the CI pre-warm pattern
    from docs/performance.md."""
    from repro.core.autotune import tune_sweep

    prof = default_profile()
    progs = [k.program for k in ks]
    hws = [mk() for mk in TOPOLOGIES.values()]
    G, H = len(progs), len(hws)
    max_steps = max(k.max_steps for k in ks)
    M = max(k.mem_init.size for k in ks)
    imgs = np.stack([np.asarray(
        np.pad(np.asarray(k.mem_init), (0, M - k.mem_init.size)))
        for k in ks]).astype(np.int32)                       # (D=G, M)
    D = imgs.shape[0]
    hw_b = stack_configs(hws)
    B = G * H * D

    # ---- packed: fresh default plan first (compile cost + zero-retrace
    # evidence), then per-bucket autotune pre-warm, then hold the tuned
    # plan for the steady-state measurement ---------------------------
    import time as _time

    fn_default = dse.make_bucketed_sweep_fn(progs, prof, hws, imgs,
                                            max_steps=max_steps,
                                            mem_size=M, backend="xla")
    buckets = fn_default.buckets
    traces0 = dse.TRACE_COUNTS["xla"]
    bucket_compile = []                # per-bucket first call: trace+jit
    for f, m, h, gi in fn_default.bucket_fns:
        t0 = _time.perf_counter()
        jax.block_until_ready(f(m, h, gi))
        bucket_compile.append(_time.perf_counter() - t0)
    traces_packed = dse.TRACE_COUNTS["xla"] - traces0

    chunks = [c for c in ((32, 64) if SMOKE else (32, 64, 128))
              if c <= max_steps]
    blks = sorted({32, H * D})
    cands = [dict(max_buckets=1, chunk_steps=c, blk_b=bb)
             for c in chunks for bb in blks]
    for b in buckets.batches:
        tune_sweep([b.program(g) for g in range(b.n_programs)], prof, hws,
                   imgs, backend="xla", max_steps=max_steps, mem_size=M,
                   candidates=cands, repeats=1 if SMOKE else 2)
    fn_packed = dse.make_bucketed_sweep_fn(progs, prof, hws, imgs,
                                           max_steps=max_steps, mem_size=M,
                                           backend="xla")
    run_packed = lambda: jax.block_until_ready(fn_packed())
    run_packed()                                        # warm tuned plan
    steady_p = timeit(run_packed, repeats=3, warmup=0)
    first_p = sum(bucket_compile)
    res_p = fn_packed()
    steps_p = int(np.asarray(res_p.steps_executed).sum())

    # ---- per-program loop: what the packed plan replaces --------------
    fns = [jax.jit(dse.make_sweep_fn(p, prof, max_steps=max_steps,
                                     mem_size=M, backend="xla"))
           for p in progs]
    mems_pd = jnp.asarray(np.tile(imgs, (H, 1)))             # (H*D, M)
    hw_pd = jax.tree.map(lambda x: jnp.repeat(x, D, axis=0), hw_b)

    def run_loop():
        for f in fns:
            jax.block_until_ready(f(mems_pd, hw_pd))
    first_l, steady_l = _first_and_steady(run_loop)

    rec = dict(
        G=G, H=H, D=D, B=B, backend="xla", max_steps=max_steps,
        t_max=max(p.n_instrs for p in progs),
        n_buckets=buckets.n_buckets,
        buckets=[dict(t_max=b.t_max, n_programs=b.n_programs,
                      chunk_steps=cfg.chunk_steps, blk_b=cfg.blk_b,
                      compile_seconds=sec)
                 for b, cfg, sec in zip(buckets.batches,
                                        fn_packed.bucket_cfgs,
                                        bucket_compile)],
        trace_counts_packed=traces_packed,
        packed=dict(compile_seconds=max(first_p - steady_p, 0.0),
                    steady_seconds_per_sweep=steady_p,
                    points_per_s=B / steady_p,
                    steps_per_s=steps_p / steady_p,
                    steps_executed=steps_p),
        per_program_loop=dict(compile_seconds=max(first_l - steady_l, 0.0),
                              steady_seconds_per_sweep=steady_l,
                              points_per_s=B / steady_l,
                              steps_per_s=steps_p / steady_l,
                              steps_executed=steps_p),
    )
    rec["compile_speedup"] = (rec["per_program_loop"]["compile_seconds"]
                              / max(rec["packed"]["compile_seconds"], 1e-9))
    rec["steady_ratio"] = steady_l / steady_p      # >= 1: packed wins
    for label in ("packed", "per_program_loop"):
        r = rec[label]
        rep.add(path=f"multi_kernel_g{G}_{label}", B=B,
                seconds_per_batch=r["steady_seconds_per_sweep"],
                points_per_s=r["points_per_s"],
                steps_per_s=r["steps_per_s"],
                steps_executed=r["steps_executed"],
                steps_nominal=B * max_steps,
                speedup_vs_single=(rec["steady_ratio"]
                                   if label == "packed" else 1.0),
                compile_seconds=r["compile_seconds"])
    return rec


def _bench_multi_kernel(rep: Report) -> list:
    """One row per grid scale: the historical G=3 mix and the G=8
    heterogeneous mix where bucketed packing must meet/beat the loop."""
    return [_bench_multi_kernel_one(rep, _multi_kernels()),
            _bench_multi_kernel_one(rep, _multi_kernels_g8())]


def _bench_reduction(rep: Report) -> list:
    """On-device reduction lane: million-point sweeps ship kilobytes.

    The DSE-as-a-service contract (docs/performance.md "On-device
    reduction"): a client asks for winners, not the grid, so the sweep
    carries a ``reduce=`` spec and only ``O(G*K)`` candidate values ever
    cross the device->host boundary instead of the five ``(B,)`` result
    fields.  One row per spec (top-k by EDP, latency/energy Pareto
    front), each comparing the held bucketed packed plan
    (``dse.make_bucketed_sweep_fn`` -- the service steady state) with
    and without on-device reduction over the identical grid:

      * ``bytes_full_per_sweep`` / ``bytes_reduced_per_sweep``: the
        device->host result bytes each steady-state call moves -- B*5*4
        (analytic; the unreduced fn fetches all five fields to stitch
        canonical lane order) vs ``reduced_nbytes`` (O(G*K), independent
        of B);
      * ``steady_ratio`` = unreduced/reduced steady seconds (>= 1 means
        reducing is free or better; the CI gate floors it at 0.9 --
        reduction must never cost more than 10% throughput);
      * ``reduced_matches_oracle``: the device candidates are
        bit-identical to the numpy oracle over the fetched full grid
        (the correctness half of the contract, re-checked on every
        bench run).
    """
    from repro.analysis.pareto import (REDUCED_FIELDS, ParetoFront, TopK,
                                       reduce_oracle, reduced_nbytes,
                                       spec_to_str)

    prof = default_profile()
    ks = _multi_kernels()
    progs = [k.program for k in ks]
    hws = [mk() for mk in TOPOLOGIES.values()]
    G, H = len(progs), len(hws)
    max_steps = max(k.max_steps for k in ks)
    M = max(k.mem_init.size for k in ks)
    base = np.stack([np.asarray(
        np.pad(np.asarray(k.mem_init), (0, M - k.mem_init.size)))
        for k in ks]).astype(np.int32)
    # widen the data axis so the lane count is service-sized: the
    # transfer-bytes contrast is the whole point of this lane
    imgs = np.tile(base, (4 if SMOKE else 32, 1))
    D = imgs.shape[0]
    B = G * H * D

    fn_full = dse.make_bucketed_sweep_fn(progs, prof, hws, imgs,
                                         max_steps=max_steps, mem_size=M,
                                         backend="xla")
    run_full = lambda: jax.block_until_ready(fn_full())
    res_full = run_full()                                # compile + warm
    fields = tuple(np.asarray(getattr(res_full, f))
                   for f in res_full._fields)
    prog_idx = np.repeat(np.arange(G), H * D)
    lane_idx = np.arange(B)

    rows = []
    for spec in (TopK("edp", k=8),
                 ParetoFront(axes=("latency_cc", "energy_pj"),
                             max_points=16)):
        fn_red = dse.make_bucketed_sweep_fn(progs, prof, hws, imgs,
                                            max_steps=max_steps,
                                            mem_size=M, backend="xla",
                                            reduce=spec)
        red = fn_red()                                   # compile + warm
        # steady_ratio is the gated metric, so the two sides are timed
        # *interleaved* (full, reduced, full, reduced, ...) and each
        # takes its per-round minimum: host-speed drift during the
        # measurement hits both sides equally instead of skewing the
        # ratio the way two independently-taken medians would.
        reps = 2 if SMOKE else 5
        t_full, t_red = [], []
        for _ in range(reps):
            t0 = time.perf_counter()
            run_full()
            t_full.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            fn_red()
            t_red.append(time.perf_counter() - t0)
        steady_full, steady_red = min(t_full), min(t_red)
        oracle = reduce_oracle(spec, fields, prog_idx, lane_idx, G)
        match = all(np.array_equal(np.asarray(getattr(red, f)),
                                   np.asarray(getattr(oracle, f)))
                    for f in REDUCED_FIELDS)
        bytes_full = B * 5 * 4
        bytes_red = reduced_nbytes(G, spec)
        row = dict(
            B=B, G=G, H=H, D=D, K=spec.k_out, spec=spec_to_str(spec),
            backend="xla", n_buckets=fn_red.buckets.n_buckets,
            bytes_full_per_sweep=bytes_full,
            bytes_reduced_per_sweep=bytes_red,
            bytes_ratio=bytes_full / bytes_red,
            steady_seconds_full=steady_full,
            steady_seconds_reduced=steady_red,
            steady_ratio=steady_full / steady_red,
            reduced_matches_oracle=bool(match))
        rows.append(row)
        rep.add(path=f"reduction_{spec_to_str(spec).partition(':')[0]}",
                B=B, seconds_per_batch=steady_red,
                points_per_s=B / steady_red, steps_per_s=B / steady_red,
                speedup_vs_single=row["steady_ratio"],
                bytes_ratio=round(row["bytes_ratio"], 1))
    return rows


def _bench_mapping_search(rep: Report) -> dict:
    """Mapping-as-a-sweep-axis lane: candidate generation throughput and
    what sweeping the mapping axis *buys*.

    * ``candidates_per_s``: seeded policy enumeration including the
      per-candidate DAG-oracle verification (``mapper.generate_
      candidates``) -- the host-side cost of opening the mapping axis;
    * ``edp_spread`` = worst/best candidate EDP at each candidate's best
      (hw, image) lane: how much a bad schedule costs, i.e. why mapping
      search matters (invariant-gated >= 1);
    * ``batched_vs_loop`` = per-candidate-loop / packed steady seconds
      for scoring the identical (K x H x D) grid -- the packed mapping
      axis reuses the bucketed multi-kernel machinery, so one held plan
      (<= n_buckets cached executables, ``trace_counts_packed``) must
      meet/beat K separately-held single-candidate plans exactly like
      the multi-kernel lane (CI-gated vs baseline).
    """
    from repro.analysis.pareto import TopK
    from repro.core.mapper import DAG, generate_candidates
    from repro.core.program import MappingSet

    d = DAG()
    w = d.load(16)
    for j in range(3 if SMOKE else 6):
        m = d.alu("SMUL", d.load(j), w)
        s = d.alu("SADD", m, d.load(32 + j))
        d.store(64 + j, d.alu("SRA", s, d.const(2)))
    K = 4 if SMOKE else 8

    t0 = time.perf_counter()
    cands = generate_candidates(d, K, seed=0, name="bench_axpy")
    t_enum = time.perf_counter() - t0
    ms = MappingSet.from_candidates([[c.program for c in cands]],
                                    names=["bench_axpy"])

    prof = default_profile()
    hws = ([TOPOLOGIES["baseline"](), TOPOLOGIES["a_fast_mul"]()] if SMOKE
           else [mk() for mk in TOPOLOGIES.values()])
    rng = np.random.default_rng(0)
    imgs = rng.integers(-100, 100, (2, 128)).astype(np.int32)
    H, D = len(hws), imgs.shape[0]
    B = ms.n_total * H * D
    max_steps = 128 if SMOKE else 256
    spec = TopK("edp", k=1)
    kw = dict(max_steps=max_steps, mem_size=128, backend="xla",
              reduce=spec)

    base_traces = dse.TRACE_COUNTS["xla"]
    fn_packed = dse.make_bucketed_sweep_fn(list(ms.programs), prof, hws,
                                           imgs, **kw)
    red = fn_packed()                                    # compile + warm
    traces_packed = dse.TRACE_COUNTS["xla"] - base_traces
    n_buckets = fn_packed.buckets.n_buckets

    edp = (np.asarray(red.energy_pj)[:, 0].astype(np.float64)
           * np.asarray(red.latency_cc)[:, 0])
    best_edp, worst_edp = float(edp.min()), float(edp.max())

    loop_fns = [dse.make_bucketed_sweep_fn([p], prof, hws, imgs, **kw)
                for p in ms.programs]
    for f in loop_fns:
        f()                                              # compile + warm

    # interleaved steady timing (same rationale as the reduction lane)
    reps = 2 if SMOKE else 5
    t_packed, t_loop = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn_packed()
        t_packed.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        for f in loop_fns:
            f()
        t_loop.append(time.perf_counter() - t0)
    steady_packed, steady_loop = min(t_packed), min(t_loop)

    rec = dict(K=ms.n_total, H=H, D=D, B=B, backend="xla",
               n_buckets=n_buckets, trace_counts_packed=traces_packed,
               enumerate_seconds=t_enum,
               candidates_per_s=ms.n_total / max(t_enum, 1e-9),
               all_verified=True,         # generate_candidates raises else
               best_edp=best_edp, worst_edp=worst_edp,
               edp_spread=worst_edp / max(best_edp, 1e-9),
               steady_seconds_packed=steady_packed,
               steady_seconds_loop=steady_loop,
               batched_vs_loop=steady_loop / max(steady_packed, 1e-9))
    rep.add(path="mapping_search_packed_axis", B=B,
            seconds_per_batch=steady_packed,
            points_per_s=B / steady_packed, steps_per_s=B / steady_packed,
            speedup_vs_single=rec["batched_vs_loop"],
            edp_spread=round(rec["edp_spread"], 2))
    return rec


def _bench_mem_completion(rep: Report) -> dict:
    """Seed S x P double loop vs the vectorized greedy scheduler."""
    S, P = MEM_BENCH_STEPS, 16
    rng = np.random.default_rng(0)
    is_mem = rng.random((S, P)) < 0.5
    addr = rng.integers(0, 4096, (S, P))
    hw = HwConfig(bus=1, interleaved=1, n_banks=4)
    t_vec = timeit(lambda: mem_completion_np(is_mem, addr, hw, 4096, 4),
                   repeats=5, warmup=1)
    t_loop = timeit(lambda: mem_completion_np_loop(is_mem, addr, hw, 4096, 4),
                    repeats=3, warmup=1)
    speedup = t_loop / t_vec
    rep.add(path="mem_completion_vectorized", B=f"{S}x{P}",
            seconds_per_batch=t_vec, points_per_s=S / t_vec,
            steps_per_s=S / t_vec, speedup_vs_single=speedup)
    return dict(S=S, P=P, seconds_loop=t_loop, seconds_vectorized=t_vec,
                speedup=speedup)


def _bench_recovery(rep: Report) -> dict:
    """Fault-tolerance lane: what crash-safety costs and buys.

    * checkpoint overhead: the same partitioned campaign with and
      without per-unit checkpointing (default async saves) -- the
      steady-state tax of durability (acceptance: small, <10% at the
      default unit size);
    * recovery: kill the campaign halfway (simulated by pre-populating
      half the unit checkpoints), then time a cold resume-and-finish --
      versus re-running the whole campaign from scratch.
    """
    import tempfile

    from repro.service import ResumableSweepRunner

    prof = default_profile()
    ks = _multi_kernels()
    hws = [mk() for mk in TOPOLOGIES.values()]
    mems = np.stack([np.asarray(k.mem_init) for k in ks])
    max_steps = max(k.max_steps for k in ks)
    unit_size = 4 if SMOKE else 8
    kw = dict(programs=[k.program for k in ks], profile=prof,
              hw_configs=hws, mem_images=mems, unit_size=unit_size,
              max_steps=max_steps)

    ResumableSweepRunner(**kw).run()                   # compile warmup
    t_plain = timeit(lambda: ResumableSweepRunner(**kw).run(),
                     repeats=3, warmup=0)

    def run_ckpt():
        with tempfile.TemporaryDirectory() as d:
            ResumableSweepRunner(ckpt_dir=d, **kw).run()
    t_ckpt = timeit(run_ckpt, repeats=3, warmup=0)
    overhead_pct = max(t_ckpt - t_plain, 0.0) / t_plain * 100.0

    # crash at the halfway unit, then cold resume-and-finish
    runner = ResumableSweepRunner(**kw)
    half = runner.n_units // 2
    with tempfile.TemporaryDirectory() as d:
        pre = ResumableSweepRunner(ckpt_dir=d, **kw)
        for k_ in range(half):
            pre.run_unit(k_)
        pre.mgr.wait()

        import time as _time
        t0 = _time.perf_counter()
        resumed = ResumableSweepRunner(ckpt_dir=d, **kw)
        _, resume_rep = resumed.run()
        t_recover = _time.perf_counter() - t0
    assert resume_rep.units_resumed == half

    B = runner.B
    rec = dict(B=B, unit_size=unit_size, units=runner.n_units,
               backend="xla",
               plain_seconds=t_plain, checkpointed_seconds=t_ckpt,
               checkpoint_overhead_pct=overhead_pct,
               killed_at_unit=half, resumed_units=half,
               recomputed_units=runner.n_units - half,
               recovery_seconds=t_recover,
               recovery_vs_rerun=t_plain / max(t_recover, 1e-9))
    rep.add(path="recovery_checkpointed_sweep", B=B,
            seconds_per_batch=t_ckpt, points_per_s=B / t_ckpt,
            steps_per_s=B / t_ckpt, speedup_vs_single=1.0,
            checkpoint_overhead_pct=overhead_pct)
    rep.add(path="recovery_resume_after_kill", B=B,
            seconds_per_batch=t_recover, points_per_s=B / t_recover,
            steps_per_s=B / t_recover,
            speedup_vs_single=rec["recovery_vs_rerun"])
    return rec


def _bench_transport(rep: Report) -> dict:
    """HTTP transport lane: what the chaos-hardened front end costs.

    The same G-kernel campaign runs two ways, timed interleaved (same
    rationale as the reduction lane -- the gated number is a ratio):

      * in-process -- ``SweepService.submit`` + step loop, zero copies
        (the baseline the recovery lane also builds on);
      * over HTTP -- ``SweepClient`` against a loopback
        ``SweepTransport``: JSON+base64 request encoding, ndjson
        per-unit record streaming with cursor acks, idempotent folding.

    ``overhead_ratio`` = transport/in-process steady seconds (lower is
    better; compare_bench ceiling-gates it vs baseline), broken down to
    ``overhead_ms_per_unit`` since every streamed unit record pays the
    encode/decode + socket round.  ``requests_per_s`` (healthz round
    trips) tracks the fixed per-request cost of the HTTP stack, and
    ``matches_inproc`` re-checks the folded transport arrays against
    the in-process result on every bench run (invariant-gated)."""
    from repro.service import (SweepClient, SweepRequest, SweepService,
                               SweepTransport)

    prof = default_profile()
    ks = _multi_kernels()
    progs = [k.program for k in ks]
    hws = [mk() for mk in TOPOLOGIES.values()]
    mems = np.stack([np.asarray(k.mem_init) for k in ks])
    max_steps = max(k.max_steps for k in ks)
    unit_size = 4 if SMOKE else 8
    G, H, D = len(progs), len(hws), int(mems.shape[0])
    B = G * H * D
    svc_kw = dict(slots=2, unit_size=unit_size, max_steps=max_steps,
                  mem_size=int(mems.shape[1]), backend="xla")

    # in-process side: one held service -- admissions after the first
    # campaign reuse the lru-cached sweep executables
    svc = SweepService(prof, **svc_kw)

    def run_inproc():
        rid = svc.submit(SweepRequest(programs=progs, hw_configs=hws,
                                      mem_images=mems))
        while svc.step():
            pass
        return svc.completed[rid]

    # transport side: a second identically-configured service (the
    # transport's worker thread owns it), no fault injection
    tr = SweepTransport(SweepService(prof, **svc_kw))
    host, port = tr.start()
    client = SweepClient(host, port, seed=0)
    run_transport = lambda: client.sweep(progs, hws, mems)

    res_in = run_inproc()                                 # compile + warm
    res_tr = run_transport()
    match = all(
        np.allclose(res_tr.arrays[f], np.asarray(res_in.arrays[f]),
                    rtol=1e-6, atol=0)
        if res_tr.arrays[f].dtype.kind == "f"
        else np.array_equal(res_tr.arrays[f], np.asarray(res_in.arrays[f]))
        for f in res_tr.arrays)
    units = res_tr.stats.records_folded

    reps = 2 if SMOKE else 5
    t_in, t_tr = [], []
    for _ in range(reps):
        t0 = time.perf_counter()
        run_inproc()
        t_in.append(time.perf_counter() - t0)
        t0 = time.perf_counter()
        run_transport()
        t_tr.append(time.perf_counter() - t0)
    steady_in, steady_tr = min(t_in), min(t_tr)

    n_req = 25 if SMOKE else 200
    t0 = time.perf_counter()
    for _ in range(n_req):
        client._request("GET", "/healthz")
    t_req = time.perf_counter() - t0
    tr.close()

    rec = dict(
        B=B, G=G, H=H, D=D, unit_size=unit_size, backend="xla",
        records_per_sweep=units,
        requests_per_s=n_req / max(t_req, 1e-9),
        steady_seconds_inproc=steady_in,
        steady_seconds_transport=steady_tr,
        overhead_ratio=steady_tr / max(steady_in, 1e-9),
        overhead_ms_per_unit=(max(steady_tr - steady_in, 0.0) * 1e3
                              / max(units, 1)),
        matches_inproc=bool(match))
    rep.add(path="transport_http_stream", B=B,
            seconds_per_batch=steady_tr, points_per_s=B / steady_tr,
            steps_per_s=B / steady_tr,
            speedup_vs_single=steady_in / max(steady_tr, 1e-9),
            overhead_ratio=round(rec["overhead_ratio"], 2))
    return rec


def run() -> Report:
    # Bench-local autotune cache (unless the caller pinned one): the
    # multi-kernel lane pre-warms per-bucket winners into it, and the
    # run never pollutes -- or gets skewed by -- the user-level cache.
    if "REPRO_AUTOTUNE_CACHE" not in os.environ:
        import tempfile
        os.environ["REPRO_AUTOTUNE_CACHE"] = os.path.join(
            tempfile.mkdtemp(prefix="repro-bench-"), "autotune.json")
    rep = Report("sim_throughput (design points / second)")
    rows: list = []
    _bench_backends(rep, rows)
    mk_rec = _bench_multi_kernel(rep)
    red_rec = _bench_reduction(rep)
    map_rec = _bench_mapping_search(rep)
    mem_rec = _bench_mem_completion(rep)
    rec_rec = _bench_recovery(rep)
    tr_rec = _bench_transport(rep)
    payload = dict(
        benchmark="sim_throughput",
        jax_backend=jax.default_backend(),
        pallas_interpret=jax.default_backend() != "tpu",
        smoke=SMOKE,
        sweep=rows,
        multi_kernel=mk_rec,
        reduction=red_rec,
        mapping_search=map_rec,
        mem_completion=mem_rec,
        recovery=rec_rec,
        transport=tr_rec,
    )
    JSON_PATH.write_text(json.dumps(payload, indent=2) + "\n")
    print(f"[bench] wrote {JSON_PATH}" + (" (smoke mode)" if SMOKE else ""))
    return rep


if __name__ == "__main__":
    run().print()

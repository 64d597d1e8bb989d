#!/usr/bin/env python3
"""Chip smoke test: one realistic DSE campaign on a TPU, end to end.

The campaign crosses the five MiBench kernels of ``apps/mibench.py``
with the paper's Table-2 topologies at realistic bank counts
(5 topologies x n_banks 2..256 x smul_lat {1, 3} x t_mem {1, 2, 4} =
240 hardware configurations) and each kernel's own memory image:
5 x 240 x 5 = 6,000 design points of 4,096-word memories.  It runs
through the entry points a user calls, in one process:

  a. device check -- no TPU, no work (exit 2);
  b. ``dse.sweep(backend="xla")``, unreduced, then TopK and ParetoFront
     reduced on device, each checked against ``reduce_oracle``;
  c. the same grid on the compiled Pallas engine (``interpret=False``):
     discrete fields bit-identical to b, energy/power to rtol 1e-6, and
     the executable must hold a ``tpu_custom_call``;
  d. the plain reference: each kernel on its own image under each
     topology through the single-point trace path
     (``KernelCase.run`` + ``estimator.estimate(..., "vi")``): the final
     memory passes the kernel's oracle and the sweep lane agrees;
  e. the served path: ``SweepTransport(SweepService(...))`` on loopback,
     three ``SweepClient`` requests (unreduced, TopK, Pareto) folded
     equal to b, with no degraded unit.

``--chips 4`` runs only the mesh phase instead: the campaign on a
4-device ``jax.make_mesh`` mesh for both backends, reduced and
unreduced, bit-identical to the same grid on one device, with the
unreduced result held on all four devices; then the same for a grid one
hardware configuration short (5,975 points, which 4 does not divide).

Timings printed on the way are those of this single smoke run, not
measurements.  The last line is one JSON object naming the device.

  python chip_smoke.py            # one chip
  python chip_smoke.py --chips 4  # the mesh phase on four chips
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import numpy as np  # noqa: E402

N_BANKS = (2, 4, 8, 16, 32, 64, 128, 256)
SMUL_LAT = (1, 3)
T_MEM = (1, 2, 4)
DISCRETE = ("latency_cc", "checksum", "steps_executed")
FLOATS = ("energy_pj", "power_mw")


class SmokeFailure(AssertionError):
    pass


def check(cond, what: str):
    if not cond:
        raise SmokeFailure(what)


def log(msg: str):
    print(msg, flush=True)


def campaign():
    from repro.apps import mibench
    from repro.core.characterization import default_profile
    from repro.core.hwconfig import TOPOLOGIES
    kernels = mibench.all_kernels()
    hws = [mk().replace(n_banks=nb, smul_lat=sl, t_mem=tm)
           for mk in TOPOLOGIES.values() for nb in N_BANKS
           for sl in SMUL_LAT for tm in T_MEM]
    mems = np.stack([k.mem_init for k in kernels])
    return dict(programs=[k.program for k in kernels],
                profile=default_profile(), hw_configs=hws, mem_images=mems,
                max_steps=max(k.max_steps for k in kernels),
                mem_size=int(mems.shape[1])), kernels


def timed(label: str, fn, B: int):
    """Run fn twice; the first call includes compilation."""
    import jax
    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    t1 = time.perf_counter()
    jax.block_until_ready(fn())
    t2 = time.perf_counter()
    log(f"[{label}] B={B} first_call_s={t1 - t0:.3f} "
        f"steady_s={t2 - t1:.3f} compile_s~{max(t1 - t0 - (t2 - t1), 0):.3f}"
        f" (single smoke run)")
    return out


def fields_of(res) -> dict:
    return {f: np.asarray(getattr(res, f)) for f in DISCRETE + FLOATS}


def same_sweep(want: dict, got: dict, what: str, exact_floats: bool):
    for f in DISCRETE:
        check(np.array_equal(want[f], got[f]), f"{what}: {f} differs")
    for f in FLOATS:
        if exact_floats:
            check(np.array_equal(want[f], got[f]), f"{what}: {f} differs")
        else:
            check(np.allclose(want[f], got[f], rtol=1e-6, atol=0),
                  f"{what}: {f} beyond rtol 1e-6")


def same_reduced(want, got, what: str, exact_floats: bool = True):
    from repro.analysis.pareto import REDUCED_FIELDS
    for f in REDUCED_FIELDS:
        a, b = np.asarray(getattr(want, f)), np.asarray(getattr(got, f))
        if f in FLOATS and not exact_floats:
            ok = np.allclose(a, b, rtol=1e-6, atol=0)
        else:
            ok = np.array_equal(a, b)
        check(ok, f"{what}: reduced {f} differs")


def oracle(spec, full: dict, G: int):
    from repro.analysis.pareto import RESULT_FIELDS, reduce_oracle
    B = full["latency_cc"].shape[0]
    return reduce_oracle(spec, [full[f] for f in RESULT_FIELDS],
                         np.repeat(np.arange(G), B // G),
                         np.arange(B), G)


def specs():
    from repro.analysis.pareto import ParetoFront, TopK
    return (TopK("energy_pj", 4),
            ParetoFront(("latency_cc", "energy_pj"), 16))


def phase_xla(grid, G, B):
    from repro.core import dse
    full = fields_of(timed("b xla unreduced", lambda: dse.sweep(
        **grid, backend="xla"), B))
    reduced = {}
    for spec in specs():
        got = timed(f"b xla {type(spec).__name__}", lambda: dse.sweep(
            **grid, backend="xla", reduce=spec), B)
        same_reduced(oracle(spec, full, G), got, f"b {spec}")
        reduced[spec] = got
        log(f"[b] {spec}: equal to reduce_oracle; count per kernel "
            f"{np.asarray(got.count).tolist()}, clipped "
            f"{np.asarray(got.clipped).tolist()}")
    return full, reduced


def phase_pallas(grid, full, B):
    import jax
    from repro.core import dse
    res = fields_of(timed("c pallas compiled", lambda: dse.sweep(
        **grid, backend="pallas", interpret=False), B))
    same_sweep(full, res, "c pallas vs xla", exact_floats=False)
    n_eq = int(sum((full[f] == res[f]).sum() for f in FLOATS))
    log(f"[c] latency_cc, checksum, steps_executed bit-identical to xla; "
        f"energy/power within rtol 1e-6 ({n_eq} of {2 * B} float values "
        f"bit-equal)")
    # the engine really is a kernel: lower the same sweep function
    fn = dse.make_sweep_fn(grid["programs"], grid["profile"],
                           max_steps=grid["max_steps"],
                           mem_size=grid["mem_size"], backend="pallas",
                           interpret=False, max_banks=max(N_BANKS),
                           validate=False)
    from repro.core.hwconfig import stack_configs
    hws = grid["hw_configs"]
    hw = stack_configs([hws[i % len(hws)] for i in range(128)])
    mem = jax.ShapeDtypeStruct((128, grid["mem_size"]), np.int32)
    text = jax.jit(fn).lower(mem, hw, np.zeros(128, np.int32)).compile() \
        .as_text()
    check("tpu_custom_call" in text, "c: no tpu_custom_call in the "
          "compiled Pallas sweep")
    log("[c] compiled Pallas sweep contains tpu_custom_call")


def phase_reference(grid, kernels, full):
    from repro.core import estimator
    from repro.core.hwconfig import TOPOLOGIES
    hws = grid["hw_configs"]
    G, H, D = len(kernels), len(hws), len(kernels)
    keys = [tuple(float(np.asarray(getattr(h, f))) for f in h.FIELDS)
            for h in hws]
    t0 = time.perf_counter()
    n, worst = 0, 0.0
    for g, k in enumerate(kernels):
        for name, mk in TOPOLOGIES.items():
            hw = mk()
            h = keys.index(tuple(float(np.asarray(getattr(hw, f)))
                                 for f in hw.FIELDS))
            lane = (g * H + h) * D + g
            final, trace = k.run(hw)
            mem = np.asarray(final.mem)
            check(k.check(mem), f"d: {k.name} on {name} fails its oracle")
            est = estimator.estimate(k.program, trace, grid["profile"], hw,
                                     "vi")
            ck = int((mem.astype(np.int64)
                      * (np.arange(mem.size) | 1)).sum() & 0xFFFFFFFF)
            ck = ck - (1 << 32) if ck >= 1 << 31 else ck
            steps = int(np.asarray(trace.valid).sum())
            check(int(full["latency_cc"][lane]) == est.latency_cc,
                  f"d: {k.name}/{name} latency {est.latency_cc} vs lane "
                  f"{int(full['latency_cc'][lane])}")
            check(int(full["checksum"][lane]) == ck
                  and int(full["steps_executed"][lane]) == steps,
                  f"d: {k.name}/{name} checksum/steps differ")
            # The estimator sums the trace's energy terms in one numpy
            # reduction; the sweep accumulates them step by step in
            # float32.  Every term is positive, so the sweep is within
            # the first-order float32 bound: one rounding per step's
            # accumulation, per PE in a step's sum, at most 14 in one
            # PE's term, and 2 in the final unit conversion.
            rel = abs(float(full["energy_pj"][lane]) - est.energy_pj) \
                / est.energy_pj
            bound = (steps + k.program.n_pes + 16) * 2.0 ** -24
            check(rel <= bound,
                  f"d: {k.name}/{name} energy {est.energy_pj} vs lane "
                  f"{float(full['energy_pj'][lane])}: rel {rel:.3g} > "
                  f"{bound:.3g}")
            worst = max(worst, rel / bound)
            n += 1
    log(f"[d] {n} reference points (5 kernels x 5 topologies): oracle "
        f"passes, latency/checksum/steps equal, energy within the float32 "
        f"bound (steps + n_pes + 16) * 2^-24 of the estimator, worst at "
        f"{worst:.3g} of its bound; "
        f"{time.perf_counter() - t0:.3f}s (single smoke run)")


def phase_served(grid, full, reduced, G, B):
    from repro.service import SweepClient, SweepService, SweepTransport
    # one work unit per kernel: a clipped Pareto front (max_points=16 is
    # below the front size of four of the five kernels here) merges
    # exactly only when no kernel's lanes are split between units
    svc = SweepService(grid["profile"], slots=1, unit_size=B // G,
                       pack_max_lanes=B, max_steps=grid["max_steps"],
                       mem_size=grid["mem_size"], backend="pallas",
                       runner_kw=dict(interpret=False))
    tr = SweepTransport(svc)
    host, port = tr.start()
    try:
        client = SweepClient(host, port, seed=0)
        args = (grid["programs"], grid["hw_configs"], grid["mem_images"])
        t0 = time.perf_counter()
        res = client.sweep(*args)
        log(f"[e] unreduced request B={B}: "
            f"{time.perf_counter() - t0:.3f}s (single smoke run)")
        check(not res.degraded_units, f"e: degraded units "
              f"{res.degraded_units}")
        same_sweep(full, res.arrays, "e served unreduced",
                   exact_floats=False)
        for spec in specs():
            t0 = time.perf_counter()
            r = client.sweep(*args, reduce=spec)
            log(f"[e] {spec} request: {time.perf_counter() - t0:.3f}s "
                f"(single smoke run)")
            check(not r.degraded_units, f"e: degraded units "
                  f"{r.degraded_units}")
            same_reduced(reduced[spec], r.reduced(), f"e served {spec}",
                         exact_floats=False)
    finally:
        tr.close()
    log("[e] 3 served requests folded equal to dse.sweep; 0 degraded "
        "units")


def phase_mesh(grid, B):
    """The mesh is the one users build, ``jax.make_mesh`` (Explicit
    axes).  Shards split kernels' lanes between devices, and a sharded
    Pareto front is exact only when no shard overflows ``max_points``, so
    this phase ranks by a front sized above every kernel's (asserted).
    A second grid, one hardware configuration short, has a lane count
    that 4 does not divide, so the padded path runs as well."""
    import jax
    from repro.analysis.pareto import ParetoFront, TopK
    from repro.core import dse
    devices = jax.devices()
    check(len(devices) >= 4, f"--chips 4: found {len(devices)} devices")
    mesh = jax.make_mesh((4,), ("data",), devices=devices[:4])
    odd = dict(grid, hw_configs=grid["hw_configs"][:-1])
    B_odd = B // len(grid["hw_configs"]) * len(odd["hw_configs"])
    check(B_odd % 4 != 0, f"odd grid B={B_odd} divides 4")
    for backend in ("xla", "pallas"):
        run = dict(backend=backend, max_buckets=1,
                   interpret=False if backend == "pallas" else None)
        for g, b, tag, reds in (
                (grid, B, "", (TopK("energy_pj", 4),
                               ParetoFront(("latency_cc", "energy_pj"),
                                           512))),
                (odd, B_odd, " odd", (TopK("energy_pj", 4),))):
            kw = dict(g, **run)
            one = dse.sweep(**kw)
            four = timed(f"mesh {backend}{tag} 4 devices",
                         lambda: dse.sweep(**kw, mesh=mesh), b)
            check(four.latency_cc.shape == (b,), f"mesh {backend}{tag}: "
                  f"shape {four.latency_cc.shape}")
            held = {s.device for s in four.latency_cc.addressable_shards}
            check(held == set(devices[:4]), f"mesh {backend}{tag}: result "
                  f"shards on {len(held)} devices")
            same_sweep(fields_of(one), fields_of(four),
                       f"mesh {backend}{tag}", exact_floats=True)
            for spec in reds:
                r1 = dse.sweep(**kw, reduce=spec)
                r4 = dse.sweep(**kw, reduce=spec, mesh=mesh)
                check(not np.asarray(r4.clipped).any(),
                      f"mesh {backend}{tag} {spec} clipped")
                same_reduced(r1, r4, f"mesh {backend}{tag} {spec}")
            log(f"[mesh] {backend} B={b}: unreduced and "
                f"{', '.join(type(s).__name__ for s in reds)} bit-identical "
                f"on 4 devices and 1; unreduced shards on all 4 devices")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the 4-device mesh phase")
    args = ap.parse_args(argv)

    import jax
    from repro.compile_cache import enable_compile_cache
    dev = jax.devices()
    if dev[0].platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev[0].platform}); "
              f"nothing run", file=sys.stderr)
        return 2
    log(f"[a] device_kind={dev[0].device_kind} count={len(dev)}")
    log(f"[a] compile cache: {enable_compile_cache()}")

    grid, kernels = campaign()
    G, H, D = (len(grid["programs"]), len(grid["hw_configs"]),
               len(grid["mem_images"]))
    B = G * H * D
    log(f"[a] campaign G={G} H={H} D={D} B={B} mem_size={grid['mem_size']}"
        f" max_steps={grid['max_steps']}")
    if args.chips == 4:
        phase_mesh(grid, B)
    else:
        full, reduced = phase_xla(grid, G, B)
        phase_pallas(grid, full, B)
        phase_reference(grid, kernels, full)
        phase_served(grid, full, reduced, G, B)
    print(json.dumps({"ok": True, "device": {
        "platform": dev[0].platform, "kind": dev[0].device_kind,
        "count": len(dev)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())

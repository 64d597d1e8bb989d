"""The campaign plan (``dse.make_bucketed_sweep_fn``) is the one path of
every in-process campaign: ``dse.sweep`` is one call of it, on a mesh as
well as on one device; a bare ``Program`` runs the cached operand core
with G = 1; and ``backend=AUTO`` reads the autotune cache without timing
anything inside the call.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import pareto
from repro.core import autotune, dse
from repro.core.autotune import AUTO, ShapeClass, TunedConfig
from repro.core.hwconfig import TOPOLOGIES
from repro.core.isa import asm
from repro.core.program import ProgramBuilder, bucket_programs

MEM = 256
MAX_STEPS = 48
SPEC = pareto.ParetoFront(("latency_cc", "energy_pj"), 8)


def _loop_program(iters, name, stride=1):
    pb = ProgramBuilder(16, name)
    pb.instr({0: asm("MV", "R1", "IMM", imm=iters)})
    top = pb.instr({0: asm("SADD", "R0", "R0", "IMM", imm=stride),
                    3: asm("SADD", "R0", "R0", "IMM", imm=3)})
    pb.instr({0: asm("SWI", a="R0", b="R0"),
              3: asm("SWI", a="R0", b="R0"),
              7: asm("SMUL", "R2", "RCL", "IMM", imm=5)})
    pb.instr({0: asm("BLT", a="R0", b="R1", imm=top)})
    pb.exit()
    return pb.build()


def _short_program(name, addr=7):
    pb = ProgramBuilder(16, name)
    pb.instr({0: asm("SADD", "R0", "R0", "IMM", imm=2),
              5: asm("LWD", "R1", imm=addr)})
    pb.instr({1: asm("SWD", a="R0", imm=addr)})
    pb.exit()
    return pb.build()


def _programs():
    """Two length classes (5 and 3 instructions): two buckets."""
    return [_loop_program(10, "l0"), _short_program("s0"),
            _loop_program(4, "l1", stride=2), _short_program("s1", addr=9)]


def _images():
    return np.stack([np.zeros(MEM, np.int32),
                     np.arange(MEM, dtype=np.int32)])


def _campaign(profile, **kw):
    return dict(dict(profile=profile,
                     hw_configs=[mk() for mk in TOPOLOGIES.values()],
                     mem_images=_images(), mem_size=MEM,
                     max_steps=MAX_STEPS, backend="xla", blk_b=4), **kw)


def _assert_same(got, ref):
    fields = pareto.REDUCED_FIELDS if ref.__class__.__name__ \
        == "ReducedResult" else ref._fields
    for f in fields:
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(ref, f)),
                                      err_msg=f)


@pytest.fixture(autouse=True)
def _empty_autotune_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "tune.json"))


@pytest.mark.parametrize("max_buckets", [1, 4])
@pytest.mark.parametrize("reduce", [None, SPEC], ids=["raw", "reduced"])
def test_held_plan_on_a_mesh_equals_sweep(profile, reduce, max_buckets):
    """A plan held with ``mesh=`` answers what ``sweep(mesh=)`` does, call
    after call, and its integer fields equal the unsharded sweep's."""
    mesh = jax.make_mesh((1,), ("data",))
    kw = _campaign(profile, reduce=reduce, max_buckets=max_buckets)
    fn = dse.make_bucketed_sweep_fn(_programs(), **kw, mesh=mesh)
    assert fn.buckets.n_buckets == min(max_buckets, 2)
    ref = dse.sweep(programs=_programs(), **kw, mesh=mesh)
    _assert_same(fn(), ref)
    _assert_same(fn(), ref)
    flat = dse.sweep(programs=_programs(), **kw)
    for f in ("indices", "count", "latency_cc") if reduce \
            else ("latency_cc", "checksum", "steps_executed"):
        np.testing.assert_array_equal(np.asarray(getattr(ref, f)),
                                      np.asarray(getattr(flat, f)),
                                      err_msg=f)


def test_held_plan_on_8_devices_pads_and_merges():
    """8 forced host devices (a subprocess: the flag must be set before
    jax imports).  The 40-lane grid and its 20-lane buckets do not
    divide 8, so every bucket pads; the held plan equals ``sweep(mesh=)``
    exactly and the unsharded sweep up to float32 order, raw (one
    bucket, several buckets) and reduced."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np
        from repro.analysis.pareto import ParetoFront, REDUCED_FIELDS
        from repro.core import dse
        from repro.core.characterization import default_profile
        from tests.test_campaign_plan import _images, _programs
        from repro.core.hwconfig import TOPOLOGIES

        mesh = jax.make_mesh((8,), ("data",))
        kw = dict(profile=default_profile(),
                  hw_configs=[mk() for mk in TOPOLOGIES.values()],
                  mem_images=_images(), mem_size=256, max_steps=48,
                  backend="xla")
        spec = ParetoFront(("latency_cc", "energy_pj"), 8)
        for max_buckets, reduce in ((1, None), (4, None), (4, spec)):
            case = dict(kw, max_buckets=max_buckets, reduce=reduce)
            fn = dse.make_bucketed_sweep_fn(_programs(), **case, mesh=mesh)
            got = fn()
            ref = dse.sweep(programs=_programs(), **case, mesh=mesh)
            flat = dse.sweep(programs=_programs(), **case)
            fields = REDUCED_FIELDS if reduce else got._fields
            for f in fields:
                a = np.asarray(getattr(got, f))
                tag = f"buckets={max_buckets} reduce={reduce} {f}"
                np.testing.assert_array_equal(
                    a, np.asarray(getattr(ref, f)), err_msg=tag)
                b = np.asarray(getattr(flat, f))
                if f in ("energy_pj", "power_mw"):
                    np.testing.assert_allclose(a, b, rtol=1e-6, err_msg=tag)
                else:
                    np.testing.assert_array_equal(a, b, err_msg=tag)
        print("HELD_MESH_OK")
    """)
    env = dict(os.environ, PYTHONPATH="src")
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", prog], env=env,
                       cwd=str(Path(__file__).resolve().parents[1]),
                       capture_output=True, text=True)
    assert "HELD_MESH_OK" in r.stdout, (r.stdout[-1500:], r.stderr[-1500:])


def test_bare_program_runs_the_operand_core(profile):
    """A bare ``Program`` on xla is ``programs=[p]`` bit for bit, through
    ``sweep`` and through ``make_sweep_fn``'s ``fn(mem, hw)``; a second
    kernel of the same shape reuses the compiled core."""
    p, q = _loop_program(10, "p"), _loop_program(4, "q", stride=2)
    assert p.n_instrs == q.n_instrs
    kw = _campaign(profile)
    bare = dse.sweep(p, **kw)
    _assert_same(bare, dse.sweep(programs=[p], **kw))
    plan = dse.plan_grid(p, kw["hw_configs"], kw["mem_images"])
    fn = dse.make_sweep_fn(p, profile, mem_size=MEM, max_steps=MAX_STEPS)
    mems = jnp.take(plan.images, jnp.asarray(plan.img_idx), axis=0)
    _assert_same(fn(mems, plan.hw_grid), bare)
    traces = dse.TRACE_COUNTS["xla"]
    other = dse.sweep(q, **kw)
    assert dse.TRACE_COUNTS["xla"] == traces, "same-shape kernel retraced"
    assert not np.array_equal(np.asarray(other.latency_cc),
                              np.asarray(bare.latency_cc))


@pytest.mark.parametrize("winner", [None, "xla", "pallas"])
def test_auto_backend_reads_the_cache_and_times_nothing(profile, monkeypatch,
                                                        winner):
    """``backend=AUTO`` takes the cached xla-vs-pallas winner of the
    campaign's shape class, else ``"xla"``; the call tunes and stores
    nothing."""
    progs = _programs()
    kw = _campaign(profile, interpret=True)
    if winner is not None:
        autotune.default_cache().store(
            ShapeClass(G=len(progs), t_max=max(p.n_instrs for p in progs),
                       H=len(kw["hw_configs"]),
                       D=int(kw["mem_images"].shape[0]), backend=AUTO),
            TunedConfig(blk_b=4, chunk_steps=16, max_buckets=2,
                        source="tuned", backend=winner))

    def no_timing(*a, **k):
        raise AssertionError("a sweep call tuned or stored a config")

    monkeypatch.setattr(autotune, "tune_sweep", no_timing)
    monkeypatch.setattr(autotune.AutotuneCache, "store", no_timing)
    seen = []
    make = dse.make_sweep_fn

    def spy(*a, **k):
        seen.append(k["backend"])
        return make(*a, **k)

    monkeypatch.setattr(dse, "make_sweep_fn", spy)
    got = dse.sweep(programs=progs, **dict(kw, backend=AUTO))
    assert seen and set(seen) == {winner or "xla"}
    monkeypatch.setattr(dse, "make_sweep_fn", make)
    ref = dse.sweep(programs=progs, **kw)
    for f in ("latency_cc", "checksum", "steps_executed"):
        np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                      np.asarray(getattr(ref, f)),
                                      err_msg=f)


@pytest.mark.parametrize("reduce", [None, SPEC], ids=["raw", "reduced"])
def test_observed_steps_sweep_equals_held_plan(profile, reduce):
    """Bucketing by trip count: a one-shot ``sweep(observed_steps=)``
    equals the held plan built with the same ``observed_steps``, and both
    bucket as ``bucket_programs`` does."""
    progs = _programs()
    observed = [40, 3, 16, 30]          # l0 and s1 run long, s0 short
    kw = _campaign(profile, reduce=reduce, max_buckets=2)
    fn = dse.make_bucketed_sweep_fn(progs, **kw, observed_steps=observed)
    assert fn.buckets.groups == bucket_programs(
        progs, 2, observed_steps=observed).groups
    assert fn.buckets.groups != bucket_programs(progs, 2).groups
    got = dse.sweep(programs=progs, **kw, observed_steps=observed)
    _assert_same(got, fn())
    if reduce is None:
        flat = dse.sweep(programs=progs, **dict(kw, max_buckets=1))
        for f in ("latency_cc", "checksum", "steps_executed"):
            np.testing.assert_array_equal(np.asarray(getattr(got, f)),
                                          np.asarray(getattr(flat, f)),
                                          err_msg=f)

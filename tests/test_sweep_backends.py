"""Backend equivalence of the DSE sweep engine.

Three independent implementations of simulate+estimate must agree:
  * the XLA scan path (core/dse.py, vmapped core/cgra.py step),
  * the fused multi-step Pallas engine (kernels/cgra_sweep, interpret
    mode on CPU CI),
  * the trace-based numpy estimator (core/estimator.py case (vi)).
Latency, checksum and steps_executed must be bit-identical; energy equal
to float32 accumulation order.  Early-exit chunking and mesh sharding
(one shard_map path for both backends) must be invisible in results.
"""
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from repro.core import dse, estimator
from repro.core.cgra import init_state, make_step, run_program
from repro.core.hwconfig import (TOPOLOGIES, HwConfig, baseline,
                                 stack_configs)
from repro.core.isa import asm
from repro.core.program import ProgramBuilder

MEM = 256
MAX_STEPS = 48


def _random_program(seed, n_instr=None):
    """Random straightline program over the full ALU + memory ISA."""
    rng = np.random.default_rng(seed)
    n_instr = n_instr or int(rng.integers(3, 10))
    alu = ["SADD", "SSUB", "SMUL", "SLL", "SRL", "SRA", "LAND", "LOR",
           "LXOR", "SLT", "MV"]
    srcs = ["ZERO", "IMM", "R0", "R1", "R2", "R3", "ROUT",
            "RCL", "RCR", "RCT", "RCB"]
    dests = ["R0", "R1", "R2", "R3", "ROUT"]
    pb = ProgramBuilder(16, f"rand{seed}")
    for _ in range(n_instr):
        slots = {}
        for p in range(16):
            if rng.random() < 0.4:
                continue
            kind = rng.choice(["alu", "alu", "lwd", "swd", "lwi", "swi"])
            imm = int(rng.integers(-2**31, 2**31 - 1))
            addr = int(rng.integers(0, MEM))
            d = str(rng.choice(dests))
            a = str(rng.choice(srcs))
            b = str(rng.choice(srcs))
            if kind == "alu":
                slots[p] = asm(str(rng.choice(alu)), d, a, b, imm)
            elif kind == "lwd":
                slots[p] = asm("LWD", d, imm=addr)
            elif kind == "swd":
                slots[p] = asm("SWD", a=a, imm=addr)
            elif kind == "lwi":
                slots[p] = asm("LWI", d, a, imm=addr)
            else:
                slots[p] = asm("SWI", a=a, b=b, imm=addr)
        pb.instr(slots)
    pb.exit()
    mem = rng.integers(-2**31, 2**31 - 1, MEM).astype(np.int32)
    return pb.build(), mem


def _loop_program(iters=10):
    """Counter loop with a store per iteration (exercises branches, the
    contention model and store arbitration across many steps)."""
    pb = ProgramBuilder(16, "loop")
    pb.instr({0: asm("MV", "R1", "IMM", imm=iters)})
    top = pb.instr({0: asm("SADD", "R0", "R0", "IMM", imm=1),
                    3: asm("SADD", "R0", "R0", "IMM", imm=3)})
    pb.instr({0: asm("SWI", a="R0", b="R0"),
              3: asm("SWI", a="R0", b="R0"),
              7: asm("SMUL", "R2", "RCL", "IMM", imm=5)})
    pb.instr({0: asm("BLT", a="R0", b="R1", imm=top)})
    pb.exit()
    return pb.build(), np.zeros(MEM, np.int32)


def _hw_batch():
    hws = [mk() for mk in TOPOLOGIES.values()]
    hws.append(HwConfig(bus=1, interleaved=1, n_banks=2, dma_per_pe=1,
                        t_mem=4, smul_lat=2))
    # t_clk_ns differing from the profile's: energy conversion must come
    # from the characterization profile on every backend
    hws.append(HwConfig(t_clk_ns=5.0))
    return hws


def _run_backend(program, mem_images, hws, backend, mem_size=MEM, **kw):
    fn = dse.make_sweep_fn(program, kw.pop("profile"), mem_size=mem_size,
                           max_steps=MAX_STEPS, backend=backend, **kw)
    B = len(hws)
    mems = jnp.asarray(np.broadcast_to(
        mem_images, (B, mem_images.size)).copy())
    return jax.tree.map(np.asarray, fn(mems, stack_configs(hws)))


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_pallas_matches_xla_random_programs(seed, profile):
    program, mem = _random_program(seed)
    hws = _hw_batch()
    rx = _run_backend(program, mem, hws, "xla", profile=profile)
    rp = _run_backend(program, mem, hws, "pallas", profile=profile,
                      blk_b=4, interpret=True)
    np.testing.assert_array_equal(rx.latency_cc, rp.latency_cc)
    np.testing.assert_array_equal(rx.checksum, rp.checksum)
    np.testing.assert_allclose(rx.energy_pj, rp.energy_pj, rtol=1e-5)
    np.testing.assert_allclose(rx.power_mw, rp.power_mw, rtol=1e-5)


@pytest.mark.parametrize("mem_size", [100, 250])
def test_pallas_matches_xla_unaligned_mem_size(mem_size, profile):
    """A memory size that is not a multiple of the 8-word VMEM row block:
    addresses wrap at mem_size and blocked banks split mem_size, not the
    padded height."""
    program, mem = _random_program(0)
    hws = _hw_batch() + [
        HwConfig(bus=1, interleaved=0, n_banks=nb, dma_per_pe=1, t_mem=2)
        for nb in (3, 4, 8)]
    mem = mem[:mem_size]
    rx = _run_backend(program, mem, hws, "xla", mem_size, profile=profile)
    rp = _run_backend(program, mem, hws, "pallas", mem_size, profile=profile,
                      blk_b=4, interpret=True)
    np.testing.assert_array_equal(rx.latency_cc, rp.latency_cc)
    np.testing.assert_array_equal(rx.checksum, rp.checksum)
    np.testing.assert_array_equal(rx.steps_executed, rp.steps_executed)
    np.testing.assert_allclose(rx.energy_pj, rp.energy_pj, rtol=1e-5)


def test_pallas_matches_xla_loop_kernel(profile):
    program, mem = _loop_program()
    hws = _hw_batch()
    rx = _run_backend(program, mem, hws, "xla", profile=profile)
    rp = _run_backend(program, mem, hws, "pallas", profile=profile,
                      blk_b=4, interpret=True)
    np.testing.assert_array_equal(rx.latency_cc, rp.latency_cc)
    np.testing.assert_array_equal(rx.checksum, rp.checksum)
    np.testing.assert_allclose(rx.energy_pj, rp.energy_pj, rtol=1e-5)


@pytest.mark.parametrize("backend,kw", [
    ("xla", {}),
    ("pallas", dict(blk_b=4, interpret=True)),
])
def test_backends_match_trace_estimator(backend, kw, profile):
    """Both fused backends == the independent trace-based estimator."""
    program, mem = _random_program(7)
    final, trace = run_program(program, mem, max_steps=MAX_STEPS,
                               mem_size=MEM)
    ref = estimator.estimate(program, trace, profile, baseline(), "vi",
                             mem_size=MEM)
    got = _run_backend(program, mem, [baseline()], backend,
                       profile=profile, **kw)
    assert int(got.latency_cc[0]) == ref.latency_cc
    np.testing.assert_allclose(float(got.energy_pj[0]), ref.energy_pj,
                               rtol=1e-4)


@pytest.mark.parametrize("chunk", [None, 5, 8, MAX_STEPS, 4096])
def test_xla_chunking_invisible_in_results(chunk, profile):
    """Early-exit chunking (any chunk size, divisor or not) must return
    results identical to the full-length scan."""
    program, mem = _loop_program()
    hws = _hw_batch()
    ref = _run_backend(program, mem, hws, "xla", profile=profile,
                       chunk_steps=None)
    got = _run_backend(program, mem, hws, "xla", profile=profile,
                       chunk_steps=chunk)
    np.testing.assert_array_equal(ref.latency_cc, got.latency_cc)
    np.testing.assert_array_equal(ref.checksum, got.checksum)
    np.testing.assert_array_equal(ref.energy_pj, got.energy_pj)


@pytest.mark.parametrize("chunk", [5, 16, MAX_STEPS])
def test_pallas_chunking_invisible_in_results(chunk, profile):
    program, mem = _loop_program()
    hws = _hw_batch()
    ref = _run_backend(program, mem, hws, "xla", profile=profile,
                       chunk_steps=None)
    got = _run_backend(program, mem, hws, "pallas", profile=profile,
                       chunk_steps=chunk, blk_b=4, interpret=True)
    np.testing.assert_array_equal(ref.latency_cc, got.latency_cc)
    np.testing.assert_array_equal(ref.checksum, got.checksum)
    np.testing.assert_allclose(ref.energy_pj, got.energy_pj, rtol=1e-5)


def test_pallas_batch_padding(profile):
    """B not a multiple of blk_b: padded lanes must not perturb results."""
    program, mem = _random_program(11)
    hws = _hw_batch()[:5]                      # B=5, blk_b=4 -> pad 3
    rx = _run_backend(program, mem, hws, "xla", profile=profile)
    rp = _run_backend(program, mem, hws, "pallas", profile=profile,
                      blk_b=4, interpret=True)
    np.testing.assert_array_equal(rx.latency_cc, rp.latency_cc)
    np.testing.assert_array_equal(rx.checksum, rp.checksum)


# ---------------------------------------------------------------------------
# True step accounting: SweepResult.steps_executed
# ---------------------------------------------------------------------------

def _steps_oracle(program, mem, hw, max_steps):
    """Host Python loop over the single-instruction transition: the
    simplest possible executed-step count, independent of scan/while_loop
    chunking on either backend."""
    step = make_step(program, 4, 4, MEM)
    state = init_state(jnp.asarray(mem, jnp.int32), program.n_pes)
    n = 0
    for _ in range(max_steps):
        if bool(state.done):
            break
        state, _ = step(state, hw)
        n += 1
    return n


@pytest.mark.parametrize("backend,kw", [
    ("xla", {}),
    ("pallas", dict(blk_b=4, interpret=True)),
])
def test_steps_executed_matches_python_loop_oracle(backend, kw, profile):
    """Early-exiting kernel: steps_executed must be the true executed
    count, not the max_steps nominal."""
    program, mem = _loop_program()
    hws = _hw_batch()
    got = _run_backend(program, mem, hws, backend, profile=profile, **kw)
    for i, hw in enumerate(hws):
        expect = _steps_oracle(program, mem, hw, MAX_STEPS)
        assert expect < MAX_STEPS          # the kernel really early-exits
        assert int(got.steps_executed[i]) == expect


@pytest.mark.parametrize("backend,kw", [
    ("xla", dict(chunk_steps=None)),
    ("xla", dict(chunk_steps=5)),
    ("pallas", dict(chunk_steps=7, blk_b=4, interpret=True)),
])
def test_steps_executed_invisible_to_chunking(backend, kw, profile):
    """Chunk overshoot must not inflate steps_executed: frozen lanes do
    not count."""
    program, mem = _loop_program()
    hws = _hw_batch()
    ref = _run_backend(program, mem, hws, "xla", profile=profile,
                       chunk_steps=MAX_STEPS)
    got = _run_backend(program, mem, hws, backend, profile=profile, **kw)
    np.testing.assert_array_equal(ref.steps_executed, got.steps_executed)


def test_steps_executed_caps_at_max_steps(profile):
    """A kernel that never EXITs within the budget reports exactly
    max_steps."""
    program, mem = _loop_program(iters=10**6)
    got = _run_backend(program, mem, [baseline()], "xla", profile=profile)
    assert int(got.steps_executed[0]) == MAX_STEPS


# ---------------------------------------------------------------------------
# Mesh-sharded sweeps: pallas under shard_map == single-device xla
# ---------------------------------------------------------------------------

def test_sweep_sharded_pallas_one_device_mesh(profile):
    """backend='pallas' under a 1-device mesh: the shard_map path must be
    bit-identical to the unsharded single-device XLA sweep."""
    program, mem = _loop_program()
    hws = _hw_batch()
    mems = np.stack([mem, np.arange(MEM, dtype=np.int32)])
    mesh = jax.make_mesh((1,), ("data",))
    rp = dse.sweep(program, profile, hws, mems, mesh=mesh, mem_size=MEM,
                   max_steps=MAX_STEPS, backend="pallas", interpret=True,
                   blk_b=4)
    rx = dse.sweep(program, profile, hws, mems, mem_size=MEM,
                   max_steps=MAX_STEPS, backend="xla")
    np.testing.assert_array_equal(np.asarray(rp.latency_cc),
                                  np.asarray(rx.latency_cc))
    np.testing.assert_array_equal(np.asarray(rp.checksum),
                                  np.asarray(rx.checksum))
    np.testing.assert_array_equal(np.asarray(rp.steps_executed),
                                  np.asarray(rx.steps_executed))
    np.testing.assert_allclose(np.asarray(rp.energy_pj),
                               np.asarray(rx.energy_pj), rtol=1e-5)


def test_sweep_sharded_pallas_multi_device():
    """backend='pallas' under a 1x8 mesh (8 forced host devices, own
    process) == single-device XLA bit-for-bit, including a design-point
    count that does not divide the device count (padding path)."""
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, numpy as np
        from repro.apps import mibench
        from repro.core import dse
        from repro.core.characterization import default_profile
        from repro.core.hwconfig import TOPOLOGIES

        profile = default_profile()
        k = mibench.bitcnt(n_words=16)
        hws = [mk() for mk in TOPOLOGIES.values()]      # H=5
        mems = np.stack([k.mem_init] * 3)               # D=3 -> B=15 (pad)
        mesh = jax.make_mesh((8,), ("data",))
        rp = dse.sweep(k.program, profile, hws, mems, mesh=mesh,
                       max_steps=256, backend="pallas", interpret=True,
                       blk_b=2)
        rx = dse.sweep(k.program, profile, hws, mems, max_steps=256,
                       backend="xla")
        assert np.array_equal(np.asarray(rp.latency_cc),
                              np.asarray(rx.latency_cc))
        assert np.array_equal(np.asarray(rp.checksum),
                              np.asarray(rx.checksum))
        assert np.array_equal(np.asarray(rp.steps_executed),
                              np.asarray(rx.steps_executed))
        np.testing.assert_allclose(np.asarray(rp.energy_pj),
                                   np.asarray(rx.energy_pj), rtol=1e-5)
        assert (np.asarray(rp.steps_executed) < 256).all()
        print("SHARDED_PALLAS_OK")
    """)
    root = Path(__file__).resolve().parents[1]
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, cwd=str(root),
                       env=dict(os.environ, PYTHONPATH=str(root / "src")),
                       timeout=1200)
    assert "SHARDED_PALLAS_OK" in r.stdout, (r.stdout[-1500:],
                                             r.stderr[-1500:])


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_sweep_grid_ordering(backend, profile):
    """sweep() row h*D+d must pair hw h with image d for both backends
    (and the index-broadcast grid must not reorder anything)."""
    program, mem = _random_program(3)
    mem2 = mem.copy()
    mem2[:64] = 9999
    hws = [baseline(), HwConfig(smul_lat=1, smul_power_scale=3.0)]
    res = dse.sweep(program, profile, hws, np.stack([mem, mem2]),
                    mem_size=MEM, max_steps=MAX_STEPS, backend=backend,
                    interpret=True if backend == "pallas" else None)
    chk = np.asarray(res.checksum).reshape(2, 2)
    # same image -> same functional result regardless of hw config
    np.testing.assert_array_equal(chk[0], chk[1])
    # different images -> different checksums
    assert (chk[:, 0] != chk[:, 1]).all()


def test_compiled_pallas_tiles_are_lane_aligned(profile):
    """Mosaic takes lane tiles in multiples of 128: the default and every
    autotune candidate is one, and a compiled engine refuses any other
    tile up front (interpret mode takes any tile)."""
    from repro.core.autotune import (ShapeClass, default_blk_b,
                                     default_cache, default_candidates)
    shape = ShapeClass(G=2, t_max=8, H=4, D=2, backend="pallas")
    tiles = {default_blk_b("pallas")} | {
        c["blk_b"] for c in default_candidates(shape, 256)}
    assert all(t % 128 == 0 for t in tiles), tiles
    # an untuned shape resolves to the backend's own tile
    fresh = ShapeClass(G=3, t_max=9, H=5, D=7, backend="pallas")
    assert default_cache().resolve(fresh).blk_b % 128 == 0
    prog, _ = _random_program(3)
    with pytest.raises(ValueError, match="multiple of 128"):
        dse.make_sweep_fn([prog], profile, mem_size=MEM, backend="pallas",
                          interpret=False, blk_b=32)
    dse.make_sweep_fn([prog], profile, mem_size=MEM, backend="pallas",
                      interpret=True, blk_b=32)

"""Driver for the fused multi-step Pallas sweep engine.

``make_pallas_sweep_fn`` builds a jitted sweep with the same contract as
the XLA path built by ``core.dse.make_sweep_fn(backend="xla")``:
bit-identical latency, checksum and executed-step counts, energy equal
to float32 accumulation order.  Given a single ``Program`` it returns
``fn(mem_init (B, M), hw batched (B,))``; given a program sequence or a
``ProgramBatch`` it returns ``fn(mem_init, hw, prog_idx)`` and each lane
fetches its kernel's instructions -- one one-hot fetch per step -- from
the fused table of all G kernels inside the kernel: the program axis is
swept as data, through one compiled engine.  ``_fn`` transposes the
batch to the kernel's lane-major layout (design points on the 128-wide
lane axis) and back.

The program tables, per-program lengths and profile vectors are
*operands* of an lru-cached jitted core (one per static configuration),
so a different kernel set of the same padded shape re-uses the compiled
engine with zero retraces (observable via ``core.dse.TRACE_COUNTS``).

Chunked early exit: the host loop issues K-instruction chunks through one
``pallas_call`` each and stops as soon as every batch lane reports done,
so short kernels stop paying for ``max_steps``.  A chunk may overshoot
the ``max_steps`` budget; the kernel freezes lanes past it, keeping
results identical to a full-length scan.

``interpret=None`` auto-selects Pallas interpret mode off-TPU so the
engine (and its tests) run everywhere, including CPU CI.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ...core.autotune import PALLAS_BLK_B
from ...core.characterization import Profile
from ...core.hwconfig import HwConfig
from ...core.memory import DEFAULT_MAX_BANKS, validate_bank_bound
from ...core.program import (Program, as_program_batch, batch_tables,
                             fused_rows)
from .kernel import (HW_INT_FIELDS, LANE_ROWS, N_CTL, build_sweep_kernel,
                     fetch_planes)

LANE_WIDTH = 128     # TPU vreg lane count: the compiled lane-tile quantum


@functools.lru_cache(maxsize=None)
def _pallas_sweep_core(rows: int, cols: int, mem_size: int, t_max: int,
                       n_progs: int, k_steps: int, max_steps: int,
                       blk_b: int, interpret: bool,
                       p_idle: float, e_sw_op: float, e_sw_mux: float,
                       mulzero: float, t_clk: float):
    """One jitted Pallas sweep core per static configuration; program
    tables / lengths / profile vectors / hw / prog_idx are operands."""
    from ...core.dse import SweepResult, TRACE_COUNTS   # avoids cycle

    P = rows * cols
    T = t_max
    M = mem_size
    Mp = -(-M // 8) * 8                  # memory height in 8-word blocks
    K = k_steps

    kern = build_sweep_kernel(
        rows=rows, cols=cols, mem_size=M, n_rows=n_progs * T, k_steps=K,
        max_steps=max_steps, p_idle=p_idle, e_sw_op=e_sw_op,
        e_sw_mux=e_sw_mux, mulzero=mulzero)

    def _chunk_call(tile, start, tab, prof, lanes, hw_f, state):
        Bp = lanes.shape[1]
        smem = pl.BlockSpec(memory_space=pltpu.SMEM)
        lane = lambda n: pl.BlockSpec((n, tile), lambda i: (0, i))
        state_specs = [lane(Mp), lane(4 * P), lane(P), lane(N_CTL),
                       lane(1)]
        in_specs = ([smem] * 4
                     + [pl.BlockSpec(tab.shape, lambda i: (0, 0)),
                        lane(len(LANE_ROWS)), lane(1)] + state_specs)
        # in + out state blocks are double-buffered by the pipeline
        state_bytes = 4 * tile * (Mp + 5 * P + N_CTL + 1)
        vmem = 4 * state_bytes + 4 * tab.size + (16 << 20)
        return pl.pallas_call(
            kern, name="cgra_sweep", grid=(Bp // tile,), in_specs=in_specs,
            out_specs=state_specs,
            out_shape=[jax.ShapeDtypeStruct(x.shape, x.dtype)
                       for x in state],
            input_output_aliases={7 + i: i for i in range(len(state))},
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("parallel",),
                vmem_limit_bytes=int(vmem)),
            interpret=interpret,
        )(start, *prof, tab, lanes, hw_f, *state)

    @jax.jit
    def _fn(tab, plen, prof, mem_init: jnp.ndarray, hw: HwConfig,
            prog_idx) -> "SweepResult":
        TRACE_COUNTS["pallas"] += 1       # trace-time only: retrace probe
        mem0 = jnp.asarray(mem_init, jnp.int32)
        B = mem0.shape[0]
        # one lane tile when the batch fits it (a block equal to the whole
        # array is legal at any width), else blk_b-wide tiles
        tile = min(blk_b, -(-B // 8) * 8)
        Bp = -(-B // tile) * tile
        pad = Bp - B

        def lanes_of(x, fill=0):
            """(B,) per-lane values -> (1, Bp) lane row."""
            x = jnp.asarray(x).reshape(1, B)
            return jnp.pad(x, ((0, 0), (0, pad)), constant_values=fill)

        gi = jnp.asarray(prog_idx, jnp.int32).reshape(B)
        lanes = jnp.concatenate(
            [lanes_of(jnp.asarray(getattr(hw, f)).astype(jnp.int32), 1)
             for f in HW_INT_FIELDS]
            + [lanes_of(gi * T), lanes_of(jnp.take(plen, gi), 1)], axis=0)
        hw_f = lanes_of(jnp.asarray(hw.smul_power_scale, jnp.float32), 1)
        state = (
            jnp.pad(mem0.T, ((0, Mp - M), (0, pad))),          # mem
            jnp.zeros((4 * P, Bp), jnp.int32),                  # regs
            jnp.zeros((P, Bp), jnp.int32),                      # rout
            jnp.concatenate([                                   # ctl
                jnp.zeros((1, Bp), jnp.int32),                  #   pc
                lanes_of(jnp.zeros((B,), jnp.int32), 1),        #   done
                jnp.zeros((1, Bp), jnp.int32),                  #   t_cc
                jnp.full((1, Bp), -1, jnp.int32),               #   prev_pc
                jnp.zeros((1, Bp), jnp.int32)], axis=0),        #   n_exec
            jnp.zeros((1, Bp), jnp.float32),                    # e_acc
        )

        def cond(c):
            t0, st = c
            return (t0 < max_steps) & (jnp.min(st[3][1]) == 0)

        def body(c):
            t0, st = c
            start = jnp.full((1,), t0, jnp.int32)
            st = _chunk_call(tile, start, tab, prof, lanes, hw_f, st)
            return (t0 + K, tuple(st))

        _, st = jax.lax.while_loop(cond, body, (jnp.int32(0), state))
        mem, _, _, ctl, e_acc = st
        lat_cc = ctl[2, :B]
        e_uwcc = e_acc[0, :B]
        # clock period comes from the characterization profile, exactly as
        # in the XLA backend and the trace estimator (hw.t_clk_ns is not
        # consulted by either)
        energy_pj = e_uwcc * (jnp.float32(t_clk) * 1e-3)
        power_mw = e_uwcc / jnp.maximum(lat_cc, 1) * 1e-3
        weights = (jnp.arange(M, dtype=jnp.int32) | 1)[:, None]
        checksum = (mem[:M, :B] * weights).sum(axis=0).astype(jnp.int32)
        return SweepResult(lat_cc, energy_pj, power_mw, checksum,
                           ctl[4, :B])

    return _fn


@functools.lru_cache(maxsize=None)
def _reduced_core(core, spec, n_progs: int):
    """Fuse the segmented top-k / Pareto reducer into the sweep core.

    One jitted program per (core, reduction spec): the ``(B,)`` result
    arrays are consumed on device by ``analysis.pareto``'s segmented
    sort/scan reduction, so only the ``O(G*K)`` candidate set is ever
    materialized for the host.  Lanes with ``lane_idx < 0`` (padding)
    are masked with +inf sentinels inside the reducer."""
    from ...analysis.pareto import make_device_reducer
    red = make_device_reducer(spec, n_progs)

    @jax.jit
    def _rfn(tab, plen, prof, mem_init, hw: HwConfig, prog_idx, lane_idx):
        res = core(tab, plen, prof, mem_init, hw, prog_idx)
        return red(tuple(res), jnp.asarray(prog_idx, jnp.int32),
                   jnp.asarray(lane_idx, jnp.int32))

    return _rfn


def make_pallas_sweep_fn(program, profile: Profile, *,
                         rows: int = 4, cols: int = 4, mem_size: int = 4096,
                         max_steps: int = 2048,
                         chunk_steps: Optional[int] = 64,
                         blk_b: int = PALLAS_BLK_B,
                         interpret: Optional[bool] = None,
                         max_banks: int = DEFAULT_MAX_BANKS,
                         validate: bool = True,
                         reduce=None):
    """Build the Pallas-backed sweep function (see module docstring).

    program: ``Program`` (single-kernel API, ``fn(mem, hw)``) or a
    sequence / ``ProgramBatch`` (``fn(mem, hw, prog_idx)``).

    reduce: an ``analysis.pareto`` reduction spec (``TopK`` /
    ``ParetoFront``).  When given, the batch API becomes ``fn(mem, hw,
    prog_idx, lane_idx) -> ReducedResult`` with the per-program
    reduction fused into the same compiled program as the sweep engine
    (the full ``(B,)`` grid never leaves the device).

    blk_b: design points per lane tile.  Compiled for the TPU it must be
    a multiple of the 128-lane vreg width; a batch that fits one tile
    runs as a single tile of its own width."""
    single = isinstance(program, Program)
    batch = as_program_batch(program)
    tables = batch_tables(batch)
    P = batch.n_pes
    if P != rows * cols:
        raise ValueError(
            f"program batch {batch.names!r}: n_pes={P} does not match "
            f"the {rows}x{cols} array")
    T = batch.t_max
    G = batch.n_programs
    K = max(1, min(chunk_steps or max_steps, max_steps))
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if not interpret and (blk_b < 1 or blk_b % LANE_WIDTH):
        raise ValueError(
            f"blk_b={blk_b}: a compiled Pallas lane tile must be a "
            f"positive multiple of {LANE_WIDTH}")

    # The fused row table as bf16 byte planes (N_FETCH * P, G*T padded to
    # the lane width): every lane fetches its whole instruction with ONE
    # one-hot matmul at row prog_idx * T + pc (see kernel.py docstring).
    tab = jnp.asarray(fetch_planes(
        fused_rows(tables), -(-G * T // LANE_WIDTH) * LANE_WIDTH))
    plen = jnp.asarray(batch.n_instrs, jnp.int32)          # (G,)
    prof = (jnp.asarray(profile.p_dec, jnp.float32),
            jnp.asarray(profile.p_act, jnp.float32),
            jnp.asarray(profile.e_src, jnp.float32))

    core = _pallas_sweep_core(
        rows, cols, mem_size, T, G, K, max_steps, blk_b, bool(interpret),
        float(np.asarray(profile.p_idle)),
        float(np.asarray(profile.e_sw_op)),
        float(np.asarray(profile.e_sw_mux)),
        float(np.asarray(profile.mulzero)),
        float(np.asarray(profile.t_clk_ns)))

    if reduce is not None:
        if single:
            raise ValueError("reduce= needs the batch API; pass a "
                             "sequence of programs or a ProgramBatch")
        rcore = _reduced_core(core, reduce, G)

        def fn(mem_init: jnp.ndarray, hw: HwConfig, prog_idx, lane_idx):
            if validate:
                validate_bank_bound(hw.n_banks, max_banks,
                                    where="cgra_sweep (backend='pallas')")
            return rcore(tab, plen, prof, mem_init, hw, prog_idx, lane_idx)

        return fn

    if single:
        def fn(mem_init: jnp.ndarray, hw: HwConfig):
            if validate:
                validate_bank_bound(hw.n_banks, max_banks,
                                    where="cgra_sweep (backend='pallas')")
            gi = jnp.zeros((jnp.shape(mem_init)[0],), jnp.int32)
            return core(tab, plen, prof, mem_init, hw, gi)
    else:
        def fn(mem_init: jnp.ndarray, hw: HwConfig, prog_idx):
            if validate:
                validate_bank_bound(hw.n_banks, max_banks,
                                    where="cgra_sweep (backend='pallas')")
            return core(tab, plen, prof, mem_init, hw, prog_idx)

    return fn

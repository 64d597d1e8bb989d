"""Fused multi-step CGRA sweep engine: the Pallas kernel.

Lane-major layout.  Design points sit on the 128-wide lane axis and the
architectural state of a lane tile is a set of ``(rows, L)`` VMEM blocks:
the scratchpad memory ``(M, L)``, the register file ``(4*P, L)``, the
output registers ``(P, L)``, the control words ``(N_CTL, L)`` (PC, done,
cycle counter, previous PC, executed-step counter) and the case-(vi)
energy accumulator ``(1, L)``.  One kernel invocation advances the tile
by K CGRA instructions with all of it resident in VMEM, so HBM traffic
is amortized K-fold over the XLA scan path, which re-reads state every
step.  Every per-lane lookup is written in a form Mosaic lowers:

  * instruction fetch: the fused program table (``program.fused_rows``,
    all G kernels of the sweep) is stored as bf16 byte planes
    ``(N_FETCH * P, R)`` -- every field fits a byte except the
    immediate, which is split into four.  A lane's whole instruction is
    ONE one-hot matmul ``planes @ onehot(prog_idx * T_max + pc)`` on the
    MXU; a one-hot column sums exactly one byte, so the f32 result is
    exact.  The fetch of step k+1 is issued at the end of step k
    (double-buffered row), and the previous instruction's switch-energy
    reference rows ride in the loop carry;
  * memory: loads gather and stores scatter through 8-word row blocks
    of the ``(M, L)`` image -- one dynamic sublane slice per distinct
    block the tile touches, with an 8-way select inside it.  Stores are
    applied in ascending PE order, so the last writer wins (the
    simulator's store arbitration) without a pairwise dedup;
  * small tables (per-opcode powers, source-kind energies) are SMEM
    scalars selected by compare, never gathered;
  * the bank/DMA contention model is the ascending-PE greedy list
    scheduler of core/memory.py in closed form: a request's issue slot
    is one past the latest earlier request that shares its bank or its
    DMA engine, so no per-lane scoreboard is indexed.  Bit-identical to
    the scoreboard formulation because slots on one resource strictly
    increase in PE order.

Fused per step, on the VPU (the MXU only fetches): operand selection
(immediates, register file, own/neighbour ROUT -- torus neighbours are
static row permutations), branchless ALU dispatch over the full ISA
(``alu_select``, shared with kernels/cgra_step), the memory access,
the contention model, lockstep retire timing, branch resolution (lowest
taken PE wins) and the case-(vi) energy estimate (decode + active +
idle + operand-source + datapath-switch terms, mirroring core/dse.py).

Lanes that have executed EXIT (or exhausted the ``max_steps`` budget
mid-chunk) are frozen by masking, so a chunk is always safe to
overshoot; the host-side loop in ops.py stops issuing chunks once every
lane reports done.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from ...core import isa
from ...core.hwconfig import BUS_N_TO_M
from ...core.program import ROW_FIELDS
from ..cgra_step.kernel import alu_select

# Rows of the per-lane integer operand block (hardware descriptor plus the
# lane's program row base and true program length).
HW_INT_FIELDS = ("smul_lat", "bus", "interleaved", "n_banks",
                 "dma_per_pe", "t_mem")
LANE_ROWS = HW_INT_FIELDS + ("row_base", "n_instrs")
# Rows of the per-lane control block carried between chunks.
CTL_ROWS = ("pc", "done", "t_cc", "prev_pc", "n_exec")
N_CTL = len(CTL_ROWS)
# Byte planes of the fetch table: one per fused-row field, four for imm.
FETCH_PLANES = tuple(p for f in ROW_FIELDS
                     for p in ((f"imm{i}" for i in range(4)) if f == "imm"
                               else (f,)))
N_FETCH = len(FETCH_PLANES)
_PLANE = {p: i for i, p in enumerate(FETCH_PLANES)}


def fetch_planes(fused: np.ndarray, n_rows_pad: int) -> np.ndarray:
    """``(R, N_ROW_FIELDS, P)`` int32 fused rows -> ``(N_FETCH * P,
    n_rows_pad)`` bf16 byte planes (row ``plane * P + pe``, column = table
    row).  Every non-immediate field must fit in a byte."""
    R, _, P = fused.shape
    planes = []
    for i, f in enumerate(ROW_FIELDS):
        col = fused[:, i, :].astype(np.int64)
        if f == "imm":
            u = col & 0xFFFFFFFF
            planes += [(u >> (8 * k)) & 0xFF for k in range(4)]
        else:
            if col.min(initial=0) < 0 or col.max(initial=0) > 0xFF:
                raise ValueError(f"fused row field {f!r} does not fit a "
                                 f"byte plane")
            planes.append(col)
    out = np.zeros((N_FETCH * P, n_rows_pad), np.float32)
    out[:, :R] = np.stack(planes, axis=0).transpose(0, 2, 1).reshape(
        N_FETCH * P, R)
    return out.astype(jnp.bfloat16)


def build_sweep_kernel(*, rows: int, cols: int, mem_size: int,
                       n_rows: int, k_steps: int, max_steps: int,
                       p_idle: float, e_sw_op: float, e_sw_mux: float,
                       mulzero: float) -> Callable:
    """Build the fused K-step kernel body (closed over all static config).

    n_rows is the number of real rows of the fused table (``G * T_max``);
    row indices are clipped into it, so a padded lane can never fetch
    past the table.  mem_size is the simulated memory size (address
    modulus and bank split); the ``(Mp, L)`` VMEM image is its 8-word
    aligned height, whose padding rows no address reaches."""
    P = rows * cols
    M = mem_size
    Mp = -(-M // 8) * 8
    NBR = {name: tuple(int(i) for i in perm) for name, perm in
           isa.neighbour_index_maps(rows, cols).items()}
    OP_SMUL = isa.OP["SMUL"]
    OP_EXIT = isa.OP["EXIT"]
    OP_LWD, OP_SWD = isa.OP["LWD"], isa.OP["SWD"]
    OP_BEQ, OP_BNE = isa.OP["BEQ"], isa.OP["BNE"]
    OP_BLT, OP_BGE, OP_JUMP = isa.OP["BLT"], isa.OP["BGE"], isa.OP["JUMP"]
    BIG = np.int32(Mp)         # block index past the end: "no request"

    def lut(idx, ref, n):
        """Per-element ``ref[idx]`` for a small SMEM table."""
        out = jnp.zeros(idx.shape, jnp.float32)
        for k in range(n):
            out = jnp.where(idx == k, ref[k], out)
        return out

    def kernel(start_ref, pdec_ref, pact_ref, esrc_ref, tab_ref, lane_ref,
               hwf_ref, mem_ref, regs_ref, rout_ref, ctl_ref, eacc_ref,
               omem_ref, oregs_ref, orout_ref, octl_ref, oeacc_ref):
        L = mem_ref.shape[1]
        start = start_ref[0]
        lanes = lane_ref[...]
        smul_lat, bus, interleaved, n_banks, dma_per_pe, t_mem, base, \
            lane_len = (lanes[i:i + 1] for i in range(len(LANE_ROWS)))
        smul_scale = hwf_ref[...]
        tab = tab_ref[...]
        R = tab.shape[1]
        pe = jax.lax.broadcasted_iota(jnp.int32, (P, L), 0)
        sub8 = jax.lax.broadcasted_iota(jnp.int32, (8, L), 0)
        # contention-model constants (lane-invariant per chunk)
        nb = jnp.maximum(n_banks, 1)
        bank_words = jnp.maximum(M // nb, 1)
        dma = jnp.where(dma_per_pe > 0, pe, pe % cols)
        omem_ref[...] = mem_ref[...]

        def fetch(row):
            """(1, L) table row per lane -> dict of (P, L) int32 fields."""
            row = jnp.clip(row, 0, n_rows - 1)
            onehot = (jax.lax.broadcasted_iota(jnp.int32, (R, L), 0)
                      == row).astype(jnp.bfloat16)
            f = jnp.dot(tab, onehot, preferred_element_type=jnp.float32
                        ).astype(jnp.int32)
            plane = lambda name: f[_PLANE[name] * P:(_PLANE[name] + 1) * P]
            out = {n: plane(n) for n in ROW_FIELDS if n != "imm"}
            out["imm"] = (plane("imm0") | (plane("imm1") << 8)
                          | (plane("imm2") << 16) | (plane("imm3") << 24))
            return out

        def permute_rows(x, perm):
            return jnp.concatenate([x[i:i + 1] for i in perm], axis=0)

        def operands(sel, imm, regs, rout, nbrs):
            val = jnp.zeros_like(imm)
            val = jnp.where(sel == isa.SRC["IMM"], imm, val)
            for r in range(4):
                val = jnp.where(sel == isa.SRC[f"R{r}"],
                                regs[r * P:(r + 1) * P], val)
            val = jnp.where(sel == isa.SRC["ROUT"], rout, val)
            for name, nv in nbrs.items():
                val = jnp.where(sel == isa.SRC[name], nv, val)
            return val

        def next_block(need, blk):
            """Smallest 8-word block any pending (PE, lane) request needs,
            and that block's rows."""
            b = jnp.min(jnp.where(need > 0, blk, BIG))
            start_row = pl.multiple_of(b * 8, 8)
            return b, omem_ref[pl.ds(start_row, 8), :], start_row

        def load(addr, need0):
            """(P, L) words at addr (old memory) where need0 > 0, else 0."""
            blk, sub = addr >> 3, addr & 7

            def body(c):
                need, val = c
                b, chunk, _ = next_block(need, blk)
                hit = (need > 0) & (blk == b)
                got = jnp.zeros_like(val)
                for s in range(8):
                    got = jnp.where(sub == s, chunk[s:s + 1], got)
                return (jnp.where(hit, 0, need), jnp.where(hit, got, val))

            _, val = jax.lax.while_loop(
                lambda c: jnp.max(c[0]) > 0, body,
                (need0, jnp.zeros_like(addr)))
            return val

        def store(addr, val, need0):
            """mem[addr] = val where need0 > 0, ascending PE order."""
            blk, sub = addr >> 3, addr & 7

            def body(need):
                b, chunk, start_row = next_block(need, blk)
                hit = (need > 0) & (blk == b)
                for p in range(P):
                    chunk = jnp.where(
                        hit[p:p + 1] & (sub8 == sub[p:p + 1]),
                        val[p:p + 1], chunk)
                omem_ref[pl.ds(start_row, 8), :] = chunk
                return jnp.where(hit, 0, need)

            jax.lax.while_loop(lambda need: jnp.max(need) > 0, body, need0)

        def mem_completion(is_mem, addr):
            """Pipelined-issue contention: slot(p) = 1 + the latest slot of
            an earlier request on the same bank or DMA engine (0 if none);
            completion = slot + t_mem (core/memory.py, closed form)."""
            bank = jnp.where(interleaved > 0, addr % nb,
                             jnp.clip(addr // bank_words, 0, n_banks - 1))
            bank = jnp.where(bus == BUS_N_TO_M, bank, 0)
            slot = jnp.zeros_like(addr)
            for p in range(1, P):
                clash = (is_mem & (pe < p)
                         & ((bank == bank[p:p + 1]) | (dma == dma[p:p + 1])))
                s = jnp.max(jnp.where(clash, slot + 1, 0), axis=0,
                            keepdims=True)
                slot = jnp.where(pe == p, s, slot)
            return jnp.where(is_mem, slot + t_mem, 0)

        def step(k, carry):
            (regs, rout, pc, done, t_cc, e_acc, prev_pc, n_exec, cur,
             has_prev, p_ops, p_srcA, p_srcB) = carry
            live = (done == 0) & (start + k < max_steps)       # (1, L)
            op = cur["ops"]
            imm = cur["imm"]
            srcA, srcB = cur["srcA"], cur["srcB"]
            nbrs = {n: permute_rows(rout, perm) for n, perm in NBR.items()}
            a = operands(srcA, imm, regs, rout, nbrs)
            b = operands(srcB, imm, regs, rout, nbrs)

            # ---- memory: every load sees the memory before the stores --
            is_load = cur["is_load"] > 0
            is_store = cur["is_store"] > 0
            direct = (op == OP_LWD) | (op == OP_SWD)
            addr = jnp.where(direct, imm, a) % M
            load_val = load(addr, (is_load & live).astype(jnp.int32))
            store(addr, jnp.where(op == OP_SWD, a, b),
                  (is_store & live).astype(jnp.int32))

            # ---- ALU + writeback ----------------------------------------
            result = jnp.where(is_load, load_val, alu_select(op, a, b))
            writes = cur["writes_rout"] > 0
            rout_new = jnp.where(writes, result, rout)
            dest = cur["dest"]
            regs_new = jnp.concatenate(
                [jnp.where(writes & (dest == r), result,
                           regs[r * P:(r + 1) * P]) for r in range(4)],
                axis=0)

            # ---- timing -------------------------------------------------
            is_mem = is_load | is_store
            alu_lat = jnp.where(op == OP_SMUL, smul_lat, 1)
            busy = jnp.where(is_mem, mem_completion(is_mem, addr), alu_lat)
            lat = jnp.max(busy, axis=0, keepdims=True)

            # ---- control: the lowest-indexed taken branch wins ----------
            taken = (((op == OP_BEQ) & (a == b)) | ((op == OP_BNE) & (a != b))
                     | ((op == OP_BLT) & (a < b))
                     | ((op == OP_BGE) & (a >= b)) | (op == OP_JUMP))
            first = jnp.min(jnp.where(taken, pe, P), axis=0, keepdims=True)
            target = jnp.sum(jnp.where(pe == first, imm, 0), axis=0,
                             keepdims=True)
            next_pc = jnp.clip(jnp.where(first < P, target, pc + 1),
                               0, lane_len - 1)
            exited = jnp.max(jnp.where(op == OP_EXIT, 1, 0), axis=0,
                             keepdims=True) > 0

            # ---- fused case-(vi) energy (mirrors core/dse.py) -----------
            smul = op == OP_SMUL
            scale = jnp.where(smul, smul_scale, 1.0)
            wait = jnp.maximum(lat - busy, 0).astype(jnp.float32)
            active = jnp.maximum(busy - 1, 0).astype(jnp.float32)
            gate = jnp.where(smul & ((a == 0) | (b == 0)), mulzero, 1.0)
            prev_ok = has_prev > 0
            op_ch = (prev_ok & (op != p_ops)).astype(jnp.float32)
            a_ch = prev_ok & (srcA != p_srcA)
            b_ch = prev_ok & (srcB != p_srcB)
            e_step = (lut(op, pdec_ref, isa.N_OPS) * scale
                      + lut(op, pact_ref, isa.N_OPS) * scale * gate * active
                      + p_idle * wait
                      + lut(cur["kindA"], esrc_ref, isa.N_SRC_KINDS)
                      + lut(cur["kindB"], esrc_ref, isa.N_SRC_KINDS)
                      + op_ch * e_sw_op
                      + (a_ch.astype(jnp.float32)
                         + b_ch.astype(jnp.float32)) * e_sw_mux
                      ).sum(axis=0, keepdims=True)

            # ---- live-masked state advance ------------------------------
            new_pc = jnp.where(live, next_pc, pc)
            return (jnp.where(live, regs_new, regs),
                    jnp.where(live, rout_new, rout),
                    new_pc,
                    jnp.where(live & exited, 1, done),
                    jnp.where(live, t_cc + lat, t_cc),
                    e_acc + jnp.where(live, e_step, 0.0),
                    jnp.where(live, pc, prev_pc),
                    jnp.where(live, n_exec + 1, n_exec),
                    fetch(base + new_pc),      # the row the next step runs
                    jnp.where(live, 1, has_prev),
                    jnp.where(live, op, p_ops),
                    jnp.where(live, srcA, p_srcA),
                    jnp.where(live, srcB, p_srcB))

        ctl = ctl_ref[...]
        pc0, done0, tcc0, prev0, nexec0 = (ctl[i:i + 1] for i in range(N_CTL))
        # seed the double buffer and the carried switch-energy reference
        # rows (fetched once per chunk from the persisted prev_pc)
        pfr = fetch(base + jnp.maximum(prev0, 0))
        carry = (regs_ref[...], rout_ref[...], pc0, done0, tcc0,
                 eacc_ref[...], prev0, nexec0, fetch(base + pc0),
                 (prev0 >= 0).astype(jnp.int32),
                 pfr["ops"], pfr["srcA"], pfr["srcB"])
        (regs, rout, pc, done, t_cc, e_acc, prev_pc, n_exec,
         *_) = jax.lax.fori_loop(0, k_steps, step, carry)
        oregs_ref[...] = regs
        orout_ref[...] = rout
        octl_ref[...] = jnp.concatenate([pc, done, t_cc, prev_pc, n_exec],
                                        axis=0)
        oeacc_ref[...] = e_acc

    return kernel

"""Plain reference of one design point: the CGRA's semantics in numpy.

Written from the model's published description (a 4x4 torus of PEs with
one shared program counter; the Table-2 bus, bank and DMA timing; the
case-(vi) energy estimate) and independent of the code under test: it
imports nothing of ``repro``.  Its inputs are the instruction tables of
a kernel (the workload), a memory image, hardware configurations and the
characterization numbers of the configuration file.

Functional execution does not depend on the hardware configuration, so
each (kernel, image) pair is executed once (``execute``) and its timing
and energy are then evaluated for every hardware configuration at once
(``evaluate``).  Energy is summed in float64; ``energy_dtype="bfloat16"``
rounds every step's energy and the running sum to bfloat16 instead, the
precision control that the comparison has to reject.
"""
from __future__ import annotations

from typing import Dict, NamedTuple

import numpy as np

# Opcode and operand-source numbering of the instruction tables.
OPCODES = ("NOP", "EXIT", "SADD", "SSUB", "SMUL", "SLL", "SRL", "SRA",
           "LAND", "LOR", "LXOR", "SLT", "MV", "BEQ", "BNE", "BLT", "BGE",
           "JUMP", "LWD", "SWD", "LWI", "SWI")
OP = {name: i for i, name in enumerate(OPCODES)}
SOURCES = ("ZERO", "IMM", "R0", "R1", "R2", "R3", "ROUT", "RCL", "RCR",
           "RCT", "RCB")
# source kind of the case-(vi) operand energy: zero, immediate, own, neighbour
SRC_KIND = np.array([0, 1, 2, 2, 2, 2, 2, 3, 3, 3, 3])
ROWS = COLS = 4
N_PES = ROWS * COLS
BUS_N_TO_M = 1

_ALU = {OP[o] for o in ("SADD", "SSUB", "SMUL", "SLL", "SRL", "SRA", "LAND",
                        "LOR", "LXOR", "SLT", "MV")}
_LOADS = {OP["LWD"], OP["LWI"]}
_STORES = {OP["SWD"], OP["SWI"]}
_BRANCH = {OP["BEQ"], OP["BNE"], OP["BLT"], OP["BGE"], OP["JUMP"]}


def _torus():
    idx = np.arange(N_PES).reshape(ROWS, COLS)
    return np.stack([np.roll(idx, 1, axis=1).reshape(-1),    # left
                     np.roll(idx, -1, axis=1).reshape(-1),   # right
                     np.roll(idx, 1, axis=0).reshape(-1),    # top
                     np.roll(idx, -1, axis=0).reshape(-1)])  # bottom


_NBR = _torus()
_PE = np.arange(N_PES)


class Execution(NamedTuple):
    """What one (kernel, image) pair did, step by step.  ``S`` is the
    number of executed steps; every per-step array has ``S`` rows."""
    pcs: np.ndarray        # (S,) instruction executed at each step
    a: np.ndarray          # (S, P) operand A values
    b: np.ndarray          # (S, P) operand B values
    addr: np.ndarray       # (S, P) memory word address (0 where no request)
    mem: np.ndarray        # (M,) final memory
    checksum: int          # int32 hash of the final memory
    steps: int


def _i32(x):
    return np.asarray(x, np.int64).astype(np.uint32).view(np.int32)


def checksum(mem: np.ndarray) -> int:
    """Sum of mem[i] * (i | 1), wrapped to int32."""
    w = np.arange(mem.size, dtype=np.int64) | 1
    s = int((mem.astype(np.int64) * w).sum()) & 0xFFFFFFFF
    return s - (1 << 32) if s >= 1 << 31 else s


def _alu(op: int, a: np.ndarray, b: np.ndarray) -> np.ndarray:
    ua, ub = a.view(np.uint32), b.view(np.uint32)
    sh = (b & 31).astype(np.uint32)
    if op == OP["SADD"]:
        return (ua + ub).view(np.int32)
    if op == OP["SSUB"]:
        return (ua - ub).view(np.int32)
    if op == OP["SMUL"]:
        return (ua * ub).view(np.int32)
    if op == OP["SLL"]:
        return (ua << sh).view(np.int32)
    if op == OP["SRL"]:
        return (ua >> sh).view(np.int32)
    if op == OP["SRA"]:
        return a >> sh.astype(np.int32)
    if op == OP["LAND"]:
        return a & b
    if op == OP["LOR"]:
        return a | b
    if op == OP["LXOR"]:
        return a ^ b
    if op == OP["SLT"]:
        return (a < b).astype(np.int32)
    if op == OP["MV"]:
        return a.copy()
    raise ValueError(f"not an ALU opcode: {op}")


def _decode(tables):
    """Per instruction: the PEs of each opcode, for a short Python loop."""
    ops = np.asarray(tables["ops"])
    rows = []
    for t in range(ops.shape[0]):
        groups = {}
        for p in range(N_PES):
            groups.setdefault(int(ops[t, p]), []).append(p)
        rows.append({op: np.asarray(pes) for op, pes in groups.items()})
    return rows


def execute(tables: Dict[str, np.ndarray], mem_init: np.ndarray,
            max_steps: int) -> Execution:
    """Run one kernel on one memory image until EXIT or ``max_steps``.

    ``tables`` holds the ``(T, P)`` int arrays ``ops``, ``dest``,
    ``srcA``, ``srcB`` and ``imm``.  All PEs read their operands at the
    start of an instruction; loads see memory before this instruction's
    stores; stores to one address land in ascending PE order; the
    lowest-indexed PE with a taken branch sets the next PC, which is
    clipped to the last instruction."""
    ops = np.asarray(tables["ops"], np.int64)
    dest = np.asarray(tables["dest"], np.int64)
    srcA = np.asarray(tables["srcA"], np.int64)
    srcB = np.asarray(tables["srcB"], np.int64)
    imm = _i32(tables["imm"])
    T = ops.shape[0]
    groups = _decode(tables)
    mem = _i32(mem_init).copy()
    M = mem.size
    # operand file: ZERO, IMM, R0..R3, ROUT, RCL, RCR, RCT, RCB
    src = np.zeros((len(SOURCES), N_PES), np.int32)
    pcs, a_l, b_l, addr_l = [], [], [], []
    pc, steps = 0, 0
    while steps < max_steps:
        src[1] = imm[pc]
        a = src[srcA[pc], _PE]
        b = src[srcB[pc], _PE]
        result = np.zeros(N_PES, np.int32)
        writes = np.zeros(N_PES, bool)
        addr = np.zeros(N_PES, np.int64)
        stores = []
        taken = None
        exited = False
        for op, pes in groups[pc].items():
            if op in _ALU:
                result[pes] = _alu(op, a[pes], b[pes])
                writes[pes] = True
            elif op in _LOADS or op in _STORES:
                direct = op in (OP["LWD"], OP["SWD"])
                addr[pes] = np.mod((imm[pc] if direct else a)[pes]
                                   .astype(np.int64), M)
                if op in _LOADS:
                    result[pes] = mem[addr[pes]]
                    writes[pes] = True
                else:
                    val = a if op == OP["SWD"] else b
                    stores += [(int(p), int(addr[p]), val[p]) for p in pes]
            elif op in _BRANCH:
                if op == OP["JUMP"]:
                    fire = pes
                else:
                    cond = {OP["BEQ"]: a[pes] == b[pes],
                            OP["BNE"]: a[pes] != b[pes],
                            OP["BLT"]: a[pes] < b[pes],
                            OP["BGE"]: a[pes] >= b[pes]}[op]
                    fire = pes[cond]
                if len(fire):
                    first = int(fire.min())
                    taken = first if taken is None else min(taken, first)
            elif op == OP["EXIT"]:
                exited = True
        for _, ad, v in sorted(stores):
            mem[ad] = v
        for k in range(4):
            hit = writes & (dest[pc] == k)
            src[2 + k, hit] = result[hit]
        src[6, writes] = result[writes]
        src[7:] = src[6][_NBR]
        pcs.append(pc)
        a_l.append(a)
        b_l.append(b)
        addr_l.append(addr)
        steps += 1
        if exited:
            break
        nxt = int(imm[pc, taken]) if taken is not None else pc + 1
        pc = min(max(nxt, 0), T - 1)
    return Execution(np.asarray(pcs, np.int64), np.asarray(a_l, np.int32),
                     np.asarray(b_l, np.int32), np.asarray(addr_l, np.int64),
                     mem, checksum(mem), steps)


def _bf16(x):
    import ml_dtypes
    return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16)


def evaluate(tables: Dict[str, np.ndarray], ex: Execution,
             hw: Dict[str, np.ndarray], profile: Dict[str, np.ndarray],
             mem_size: int, energy_dtype: str = "float64",
             block: int = 1024) -> Dict[str, np.ndarray]:
    """Latency (cycles), energy (pJ) and power (mW) of one execution under
    each of ``H`` hardware configurations (``hw`` maps field -> (H,)).

    Timing: a PE's ALU op takes 1 cycle, SMUL ``smul_lat``; memory
    requests arbitrate in ascending PE order, each taking the first cycle
    at which both its bank port (one global port on the 1-to-M bus) and
    its DMA engine (one per column, or one per PE) are free; a request
    completes ``t_mem`` cycles after it issues; an instruction retires
    when its slowest PE is done."""
    ops = np.asarray(tables["ops"], np.int64)[ex.pcs]           # (S, P)
    kA = SRC_KIND[np.asarray(tables["srcA"], np.int64)][ex.pcs]
    kB = SRC_KIND[np.asarray(tables["srcB"], np.int64)][ex.pcs]
    sA = np.asarray(tables["srcA"], np.int64)[ex.pcs]
    sB = np.asarray(tables["srcB"], np.int64)[ex.pcs]
    S = ops.shape[0]
    H = len(np.asarray(hw["n_banks"]))
    p_dec = np.asarray(profile["p_dec"], np.float64)
    p_act = np.asarray(profile["p_act"], np.float64)
    e_src = np.asarray(profile["e_src"], np.float64)
    p_idle = float(profile["p_idle"])
    is_mem = np.isin(ops, sorted(_LOADS | _STORES))
    smul = ops == OP["SMUL"]

    # hardware-independent energy: operand sources and datapath switching
    prev = np.concatenate([ops[:1], ops[:-1]])
    pA = np.concatenate([sA[:1], sA[:-1]])
    pB = np.concatenate([sB[:1], sB[:-1]])
    has_prev = (np.arange(S) > 0)[:, None]
    e_fixed = ((e_src[kA] + e_src[kB]).sum(axis=1)
               + (has_prev & (ops != prev)).sum(axis=1)
               * float(profile["e_sw_op"])
               + ((has_prev & (sA != pA)).sum(axis=1)
                  + (has_prev & (sB != pB)).sum(axis=1))
               * float(profile["e_sw_mux"]))                     # (S,)
    gate = np.where(smul & ((ex.a == 0) | (ex.b == 0)),
                    float(profile["mulzero"]), 1.0)               # (S, P)

    nb = np.asarray(hw["n_banks"], np.int64)[None, :, None]
    inter = np.asarray(hw["interleaved"], np.int64)[None, :, None] > 0
    bus_nm = np.asarray(hw["bus"], np.int64)[None, :, None] == BUS_N_TO_M
    dma_pe = np.asarray(hw["dma_per_pe"], np.int64)[None, :] > 0
    t_mem = np.asarray(hw["t_mem"], np.int64)[None, :]
    smul_lat = np.asarray(hw["smul_lat"], np.int64)[None, :, None]
    scale = np.asarray(hw["smul_power_scale"], np.float64)[None, :, None]
    bank_words = np.maximum(mem_size // np.maximum(nb, 1), 1)

    lat_total = np.zeros(H, np.int64)
    e_steps = []
    for lo in range(0, S, block):
        sl = slice(lo, min(S, lo + block))
        n = sl.stop - sl.start
        req = is_mem[sl]                                          # (n, P)
        ad = ex.addr[sl][:, None, :]                              # (n, 1, P)
        bank = np.where(inter, ad % np.maximum(nb, 1),
                        np.clip(ad // bank_words, 0, nb - 1))
        bank = np.where(bus_nm, bank, 0)                          # (n, H, P)
        slot = np.zeros((n, H, N_PES), np.int64)
        for p in range(N_PES):
            if not req[:, p].any():
                continue
            best = np.zeros((n, H), np.int64)
            for q in range(p):
                both = (req[:, q] & req[:, p])[:, None]
                same = bank[:, :, q] == bank[:, :, p]
                if q % COLS == p % COLS:
                    same = same | ~dma_pe
                best = np.maximum(best, np.where(both & same,
                                                 slot[:, :, q] + 1, 0))
            slot[:, :, p] = best
        done = slot + t_mem[:, :, None]
        alu = np.where(smul[sl][:, None, :], smul_lat, 1)
        busy = np.where(req[:, None, :], done, alu)               # (n, H, P)
        lat = busy.max(axis=2)                                    # (n, H)
        lat_total += lat.sum(axis=0)
        opsl = ops[sl]
        sc = np.where(smul[sl][:, None, :], scale, 1.0)
        active = np.maximum(busy - 1, 0)
        wait = lat[:, :, None] - busy
        e = (p_dec[opsl][:, None, :] * sc
             + p_act[opsl][:, None, :] * sc * gate[sl][:, None, :] * active
             + p_idle * wait).sum(axis=2) + e_fixed[sl][:, None]  # (n, H)
        e_steps.append(e)
    e_steps = np.concatenate(e_steps) if e_steps else np.zeros((0, H))
    t_clk = float(profile["t_clk_ns"])
    if energy_dtype == "float64":
        e_total = e_steps.sum(axis=0)
        energy = e_total * (t_clk * 1e-3)
    elif energy_dtype == "bfloat16":
        acc = _bf16(np.zeros(H))
        for row in _bf16(e_steps):
            acc = _bf16(acc.astype(np.float32) + row.astype(np.float32))
        e_total = acc.astype(np.float64)
        energy = _bf16(e_total * float(_bf16(t_clk * 1e-3))).astype(
            np.float64)
    else:
        raise ValueError(f"unknown energy_dtype {energy_dtype!r}")
    power = e_total / np.maximum(lat_total, 1) * 1e-3
    return {"latency_cc": lat_total, "energy_pj": energy, "power_mw": power}


def pareto_front(lat: np.ndarray, energy: np.ndarray) -> np.ndarray:
    """Positions of the non-dominated points (<= on both axes and < on
    one dominates; exact duplicates of a front point stay), in ascending
    (latency, energy, position) order."""
    lat = np.asarray(lat, np.float64)
    energy = np.asarray(energy, np.float64)
    order = np.lexsort((np.arange(lat.size), energy, lat))
    keep, best = [], np.inf
    i = 0
    while i < order.size:
        j = i
        while j < order.size and lat[order[j]] == lat[order[i]]:
            j += 1
        grp = order[i:j]                         # one latency, by energy
        e_min = energy[grp[0]]
        if e_min < best:
            keep.extend(int(k) for k in grp if energy[k] == e_min)
            best = e_min
        i = j
    return np.asarray(keep, np.int64)

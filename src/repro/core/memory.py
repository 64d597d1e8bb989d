"""Memory-subsystem timing model: bus / bank / DMA contention.

The CGRA shares its data memory with the rest of the MCU; memory operations
therefore have *system-dependent* latency (paper Table 1, case (iii)).  The
model below is the one both the detailed reference simulator and the
case-(iii)+ estimator use -- the paper reports that once memory contention
is characterized the latency estimate matches post-synthesis exactly, so
the two paths share one formula by construction.

Mechanics (pipelined issue):
  * every memory request occupies one *issue slot* on each resource it
    needs; a resource accepts one new request per cycle;
  * resources: the DMA engine it goes through (one per column in the
    baseline, one per PE for mod (d)) and the bus/bank port
    (single global port for 1-to-M; one port per bank for N-to-M);
  * requests arbitrate in ascending PE order (greedy list scheduler);
  * completion cycle = issue_slot + t_mem.

The instruction retires when every PE has finished (lockstep), so the
instruction's latency is max(ALU latencies, memory completions).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from .hwconfig import BUS_N_TO_M, HwConfig

# Bank scoreboards need a static shape under jit, so every engine takes a
# `max_banks` bound.  The bound is *config-derived*: sweep drivers call
# ``scoreboard_bound`` on the largest n_banks they will run and get the
# next power of two, so a 32-bank design point gets a 32-slot scoreboard
# instead of silently aliasing into a 16-slot one (the old static cap
# clipped bank indices >= 16 in gather and dropped them in scatter --
# i.e. *wrong contention results with no error*).
DEFAULT_MAX_BANKS = 16   # bound used when no configs are in scope yet
HARD_MAX_BANKS = 256     # absolute ceiling (scoreboard size per lane)

# Backwards-compatible alias for pre-lift callers.
MAX_BANKS = DEFAULT_MAX_BANKS


def scoreboard_bound(n_banks_required: int) -> int:
    """Config-derived scoreboard size: next power of two >= the largest
    n_banks in the sweep.  Hard-asserts the absolute ceiling -- a config
    beyond HARD_MAX_BANKS must fail loudly, never silently alias.  (The
    raise is explicit, not a bare ``assert``, so ``python -O`` cannot
    strip the guard.)"""
    n = int(n_banks_required)
    if not 1 <= n <= HARD_MAX_BANKS:
        raise AssertionError(
            f"n_banks={n} exceeds HARD_MAX_BANKS={HARD_MAX_BANKS}: the "
            f"bank scoreboard would need {n} slots per design point; "
            f"raise HARD_MAX_BANKS deliberately or reduce the configured "
            f"bank count")
    return 1 << (n - 1).bit_length()


def _raise_over_bound(nb: int, max_banks: int, where: str) -> None:
    raise AssertionError(
        f"{where or 'sweep'}: configured n_banks={nb} exceeds the "
        f"bank scoreboard bound max_banks={max_banks}; the old code "
        f"silently aliased such configs into wrong contention "
        f"results. Pass max_banks=scoreboard_bound({nb}) or use "
        f"dse.sweep(), which derives the bound from the configs")


def validate_bank_bound(n_banks, max_banks: int, where: str = "") -> None:
    """Hard assert that every configured n_banks fits the scoreboard
    bound in use.  Concrete values fail immediately at call time; traced
    values (the caller's fn wrapped in an outer jit / shard_map) fall
    back to a runtime ``jax.debug.callback`` so an over-bound config
    still fails loudly instead of silently aliasing."""
    try:
        nb = int(np.max(np.asarray(n_banks)))
    except (jax.errors.ConcretizationTypeError,
            jax.errors.TracerArrayConversionError):
        def _runtime_check(v):
            nb = int(np.max(np.asarray(v)))
            if nb > max_banks:
                _raise_over_bound(nb, max_banks, where)
        jax.debug.callback(_runtime_check, jnp.max(jnp.asarray(n_banks)))
        return
    if nb > max_banks:
        _raise_over_bound(nb, max_banks, where)


def bank_of(addr: jnp.ndarray, hw: HwConfig, mem_size: int) -> jnp.ndarray:
    """Bank index of an address under the configured mapping."""
    n_banks = jnp.asarray(hw.n_banks, jnp.int32)
    bank_words = jnp.maximum(mem_size // jnp.maximum(n_banks, 1), 1)
    interleaved = addr % jnp.maximum(n_banks, 1)
    blocked = jnp.clip(addr // bank_words, 0, n_banks - 1)
    bank = jnp.where(jnp.asarray(hw.interleaved, jnp.int32) > 0,
                     interleaved, blocked)
    # 1-to-M bus: a single global port == everything in "bank 0".
    return jnp.where(jnp.asarray(hw.bus, jnp.int32) == BUS_N_TO_M, bank, 0)


def mem_completion_times(is_mem: jnp.ndarray, addr: jnp.ndarray,
                         hw: HwConfig, mem_size: int, cols: int,
                         max_banks: int = DEFAULT_MAX_BANKS) -> jnp.ndarray:
    """Per-PE memory completion time (cc from instruction start).

    is_mem: (P,) bool -- PE issues a memory request this instruction
    addr:   (P,) int32 -- word address of the request
    max_banks: static bank-scoreboard size; must be >= every n_banks this
    function will see (see scoreboard_bound / validate_bank_bound).
    Returns (P,) int32; 0 where no request is made.

    Greedy in-order arbitration, implemented as a P-step lax.scan so it is
    jit/vmap-friendly (vmap axes: data batch, hardware-config batch).
    """
    P = is_mem.shape[0]
    pe_idx = jnp.arange(P, dtype=jnp.int32)
    col = pe_idx % cols
    bank = bank_of(addr, hw, mem_size)
    dma = jnp.where(jnp.asarray(hw.dma_per_pe, jnp.int32) > 0, pe_idx, col)
    t_mem = jnp.asarray(hw.t_mem, jnp.int32)

    def arb(carry, x):
        bank_free, dma_free = carry          # (max_banks,), (P,)
        req, b, d = x
        slot = jnp.maximum(bank_free[b], dma_free[d])
        bank_free = jnp.where(req, bank_free.at[b].set(slot + 1), bank_free)
        dma_free = jnp.where(req, dma_free.at[d].set(slot + 1), dma_free)
        completion = jnp.where(req, slot + t_mem, 0)
        return (bank_free, dma_free), completion

    init = (jnp.zeros(max_banks, jnp.int32), jnp.zeros(P, jnp.int32))
    _, completion = jax.lax.scan(arb, init, (is_mem, bank, dma))
    return completion


def instruction_latency(op_lat: jnp.ndarray, mem_done: jnp.ndarray
                        ) -> jnp.ndarray:
    """Lockstep retire: latency = max over PEs of (ALU latency | memory
    completion)."""
    return jnp.maximum(jnp.max(op_lat), jnp.max(mem_done))


def alu_latency_table(hw: HwConfig) -> jnp.ndarray:
    """Per-opcode busy latency in cc, excluding memory contention.

    All logic/arithmetic ops take 1 cc on OpenEdgeCGRA except SMUL
    (hw.smul_lat; 3 baseline / 1 for mod (a)).  Memory ops' entries here are
    placeholders (their true time comes from mem_completion_times).
    """
    from .isa import N_OPS, OP
    lat = jnp.ones(N_OPS, jnp.int32)
    return lat.at[OP["SMUL"]].set(jnp.asarray(hw.smul_lat, jnp.int32))

"""Design-space exploration at fleet scale.

The paper's value proposition is *instantaneous comparative analysis* of
(kernel mapping x hardware topology) points.  Here that becomes a batched,
mesh-sharded computation over all THREE design-space axes:

  * the functional simulator (cgra.py) takes the program tables as a
    traced operand (``make_step_fn``) and is vmapped over the flattened
    (program x hardware x data) grid: every lane carries a ``prog_idx``
    and gathers its kernel's instruction rows from the stacked
    ``(G, T_max, P)`` tables *inside* the jitted program -- the host
    never tiles program tables, and swapping kernels never retraces;
  * the estimator's case-(vi) analytic model is fused into the
    simulation scan of ``make_sweep_fn`` as pure jnp (the inline
    estimate below, mirroring ``estimator.estimate(case="vi")``), so the
    full simulate->estimate path stays inside one jitted program -- no
    host round-trip per design point;
  * sweep() shards the flattened (program x hw x data) grid over every
    device of the mesh with one ``shard_map`` builder for both backends
    (each device sweeps its own shard): on a 512-chip pod this is a
    512-way data-parallel sweep, the deployable version of the paper's
    tool.

Different *mappings* (programs) are packed to a common padded shape by
``program.pack_programs`` and swept as data: ONE compiled executable per
backend covers the full G-kernel grid (``TRACE_COUNTS`` lets tests
assert the no-retrace property).
"""
from __future__ import annotations

import functools
from typing import Dict, NamedTuple, Optional, Sequence, Union

import jax
import jax.numpy as jnp
import numpy as np

from . import isa
from ..analysis import pareto as _pareto
from .autotune import (AUTO, ShapeClass, autotune_enabled, default_blk_b,
                       default_cache, is_auto, tune_sweep)
from .cgra import init_state, make_exec_fn, rows_from_fused
from .characterization import Profile
from .hwconfig import HwConfig, stack_configs
from .memory import (DEFAULT_MAX_BANKS, scoreboard_bound,
                     validate_bank_bound)
from .program import (MappingSet, Program, ProgramBatch, as_program_batch,
                      batch_tables, bucket_programs, fused_rows,
                      program_tables)

# Incremented once per trace of each backend's sweep body (a Python side
# effect only runs while tracing, never while executing the compiled
# program).  Tests use deltas of these to assert that sweeping G kernels
# compiles once and that same-shape program swaps hit the jit cache.
TRACE_COUNTS: Dict[str, int] = {"xla": 0, "pallas": 0}


class SweepResult(NamedTuple):
    latency_cc: jnp.ndarray      # (B,) int32
    energy_pj: jnp.ndarray       # (B,) float32
    power_mw: jnp.ndarray        # (B,) float32
    checksum: jnp.ndarray        # (B,) int32 (output-memory hash, validity)
    steps_executed: jnp.ndarray  # (B,) int32 true executed instructions
    # (not the max_steps nominal -- early-exiting kernels report what ran)


def _profile_tables(profile: Profile):
    return dict(
        lat=jnp.asarray(profile.lat, jnp.int32),
        t_mem=jnp.asarray(profile.t_mem, jnp.int32),
        p_dec=jnp.asarray(profile.p_dec, jnp.float32),
        p_act=jnp.asarray(profile.p_act, jnp.float32),
        p_idle=jnp.asarray(profile.p_idle, jnp.float32),
        e_src=jnp.asarray(profile.e_src, jnp.float32),
        e_sw_op=jnp.asarray(profile.e_sw_op, jnp.float32),
        e_sw_mux=jnp.asarray(profile.e_sw_mux, jnp.float32),
        mulzero=jnp.asarray(profile.mulzero, jnp.float32),
        t_clk_ns=jnp.asarray(profile.t_clk_ns, jnp.float32),
    )


def _norm_chunk(chunk_steps: Optional[int], max_steps: int) -> Optional[int]:
    """None (single full-length scan) or the effective chunk size."""
    if chunk_steps is None or chunk_steps >= max_steps:
        return None
    return max(1, chunk_steps)


def _sweep_body(exec_step, fused, base, n_instrs, tbl, mem_init,
                hw: HwConfig, max_steps: int, chunk: Optional[int],
                mem_size: int) -> "SweepResult":
    """One lane's fused simulate+estimate scan over the fused row table.

    ``fused`` is the ``program.fused_rows`` array -- ``(R, N_ROW_FIELDS,
    P)`` where R is ``T`` (single-program constant) or ``G * T_max``
    (stacked operand) -- and ``base`` is this lane's row offset
    (``prog_idx * T_max``; 0 for the constant path).  Each step performs
    ONE ``dynamic_slice`` row fetch at ``base + pc`` and shares the
    decoded instruction between the simulator (``cgra.make_exec_fn``)
    and the fused case-(vi) estimate; the previous instruction's
    switch-energy reference rows ride in the scan carry instead of being
    re-gathered at ``prev_pc``.  Numerically identical to the historical
    per-table-gather body."""
    fused = jnp.asarray(fused)
    P = fused.shape[-1]
    state0 = init_state(mem_init, P)
    zrow = jnp.zeros((P,), jnp.int32)
    # carried previous-instruction rows: (seen-any-live-step, ops, srcA,
    # srcB) -- exactly the rows the switch-energy terms compare against
    carry0 = (state0, jnp.float32(0.0),
              (jnp.zeros((), jnp.bool_), zrow, zrow, zrow), jnp.int32(0))

    def body(carry, t):
        state, e_acc, (has_prev, p_ops, p_srcA, p_srcB), n_exec = carry
        pc = state.pc
        live = ~state.done & (t < max_steps)
        row = jax.lax.dynamic_index_in_dim(fused, base + pc, axis=0,
                                           keepdims=False)   # (NF, P)
        instr = rows_from_fused(row)
        new_state, rec = exec_step(instr, n_instrs, state, hw, live=live)
        # ---- fused case-(vi) estimate (mirrors estimator.py) --------------
        ops = instr.ops
        smul = ops == isa.OP["SMUL"]
        scale = jnp.where(smul, jnp.asarray(hw.smul_power_scale,
                                            jnp.float32), 1.0)
        # Timing reuses the simulator's (case-iii-identical) model; the
        # standalone estimator.py recomputes it independently.
        busy = rec.busy
        lat = rec.lat
        wait = jnp.maximum(lat - busy, 0).astype(jnp.float32)
        active = jnp.maximum(busy - 1, 0).astype(jnp.float32)
        gate = jnp.where(smul & ((rec.a == 0) | (rec.b == 0)),
                         tbl["mulzero"], 1.0)
        op_ch = has_prev & (ops != p_ops)
        a_ch = has_prev & (instr.srcA != p_srcA)
        b_ch = has_prev & (instr.srcB != p_srcB)
        e_step = (tbl["p_dec"][ops] * scale
                  + tbl["p_act"][ops] * scale * gate * active
                  + tbl["p_idle"] * wait
                  + tbl["e_src"][instr.kindA]
                  + tbl["e_src"][instr.kindB]
                  + op_ch * tbl["e_sw_op"]
                  + (a_ch.astype(jnp.float32) + b_ch.astype(jnp.float32))
                  * tbl["e_sw_mux"]).sum()
        e_acc = e_acc + jnp.where(live, e_step, 0.0)
        prev = (has_prev | live,
                jnp.where(live, ops, p_ops),
                jnp.where(live, instr.srcA, p_srcA),
                jnp.where(live, instr.srcB, p_srcB))
        n_exec = n_exec + live.astype(jnp.int32)
        return (new_state, e_acc, prev, n_exec), None

    if chunk is None:
        carry, _ = jax.lax.scan(
            body, carry0, jnp.arange(max_steps, dtype=jnp.int32))
    else:
        K = chunk

        def chunk_cond(c):
            t0, (state, _, _, _) = c
            return (t0 < max_steps) & ~state.done

        def chunk_body(c):
            t0, carry = c
            carry, _ = jax.lax.scan(
                body, carry, t0 + jnp.arange(K, dtype=jnp.int32))
            return (t0 + K, carry)

        _, carry = jax.lax.while_loop(chunk_cond, chunk_body,
                                      (jnp.int32(0), carry0))
    final, e_uwcc, _, n_exec = carry
    lat_cc = final.t_cc
    # one rounding of (t_clk * 1e-3) whether the profile is an operand or
    # a folded constant (a mesh sweep closes over it): bit-identical
    # energies on 1 and N devices
    energy_pj = e_uwcc * (tbl["t_clk_ns"] * 1e-3)
    power_mw = e_uwcc / jnp.maximum(lat_cc, 1) * 1e-3
    checksum = (final.mem * (jnp.arange(mem_size, dtype=jnp.int32) | 1)
                ).sum().astype(jnp.int32)
    return SweepResult(lat_cc, energy_pj, power_mw, checksum, n_exec)


@functools.lru_cache(maxsize=None)
def _xla_sweep_core(rows: int, cols: int, mem_size: int, max_steps: int,
                    chunk: Optional[int], max_banks: int, t_max: int):
    """One jitted sweep core per static configuration (the multi-program
    path).

    The fused row table (``program.fused_rows``, flattened ``(G * T_max,
    N_ROW_FIELDS, P)``), per-program lengths, profile tables, memory
    images, hardware configs and per-lane program indices are all
    *operands*: a second program set (or profile) of the same padded
    shape re-uses the compiled executable -- zero retraces across
    kernels.  Each lane addresses its instruction with one
    scalar-prefetch-style row index ``prog_idx * T_max + pc`` (a single
    ``dynamic_slice`` per step) instead of materializing its own
    ``(T_max, P)`` table slice and gathering ten fields from it."""
    exec_step = make_exec_fn(rows, cols, mem_size, max_banks=max_banks)

    def one(fused, plen, tbl, mem_init, hw: HwConfig, gi):
        TRACE_COUNTS["xla"] += 1          # trace-time only: retrace probe
        base = gi * t_max
        return _sweep_body(exec_step, fused, base, plen[gi], tbl, mem_init,
                           hw, max_steps, chunk, mem_size)

    return jax.jit(jax.vmap(one, in_axes=(None, None, None, 0, 0, 0)))


def _xla_single_sweep_fn(program: Program, profile: Profile, rows: int,
                         cols: int, mem_size: int, max_steps: int,
                         chunk: Optional[int], max_banks: int):
    """Seed-style single-program sweep: the fused row table is a closure
    constant of an *unjitted* vmapped fn (the caller jits), keeping the
    constant-folding-friendly data flow -- and the compile-per-program
    cost -- of the original API.  Numerically identical to the operand
    core with G=1."""
    exec_step = make_exec_fn(rows, cols, mem_size, max_banks=max_banks)
    fused = fused_rows(program_tables(program))      # (T, NF, P) constant
    n_instrs = np.int32(program.n_instrs)
    tbl = _profile_tables(profile)

    def one(mem_init, hw: HwConfig):
        TRACE_COUNTS["xla"] += 1          # trace-time only: retrace probe
        return _sweep_body(exec_step, fused, np.int32(0), n_instrs, tbl,
                           mem_init, hw, max_steps, chunk, mem_size)

    return jax.vmap(one)


def make_sweep_fn(program: Union[Program, ProgramBatch, Sequence[Program]],
                  profile: Profile, *, rows: int = 4,
                  cols: int = 4, mem_size: int = 4096, max_steps: int = 2048,
                  backend: str = "xla", chunk_steps: Optional[int] = 64,
                  blk_b: Union[int, None, str] = AUTO,
                  interpret: Optional[bool] = None,
                  max_banks: Optional[int] = None,
                  validate: bool = True,
                  reduce: Optional[_pareto.Reduction] = None):
    """Build the fused sweep function where the case-(vi) estimate is
    fused into the simulation scan (single pass, no trace
    materialization -- O(1) memory per design point).

    program: a single ``Program`` -> ``fn(mem_init (B, M), hw batched
    (B,)) -> SweepResult`` (the original constant-closure API -- tables
    are baked in as jit constants, fastest per-program data flow, one
    compile per kernel); a sequence of programs or a ``ProgramBatch`` ->
    ``fn(mem_init (B, M), hw (B,), prog_idx (B,))`` where each lane
    gathers its kernel from the packed ``(G, T_max, P)`` tables inside
    the jitted program and the tables are runtime operands of one cached
    executable per static configuration: sweeping a different kernel set
    of the same padded shape causes NO retrace (``TRACE_COUNTS``
    observable).

    backend:
      * ``"xla"``    -- vmapped ``lax.scan`` over ``core.cgra.make_step_fn``
        (the portable path);
      * ``"pallas"`` -- the fused multi-step VMEM-resident engine
        (``kernels.cgra_sweep``): K instructions per ``pallas_call``,
        one HBM read of the stacked program tables per batch tile.
        ``interpret`` (default: auto, True off-TPU) runs it through the
        Pallas interpreter so results are testable everywhere.
    Both backends produce bit-identical latency_cc / checksum /
    steps_executed and energy equal up to float32 accumulation order.

    chunk_steps: issue the scan in K-step chunks and stop early once every
    batch lane reports done (EXIT reached) -- short kernels stop paying
    for ``max_steps``.  ``None`` disables chunking (single full-length
    scan); results are identical either way.

    blk_b: batch tile.  On Pallas it is the VMEM lane tile of each
    ``pallas_call``; on the XLA operand path it is the lane-block size of
    the eager dispatch (cache-residency -- see the comment in ``fn``),
    autotunable per shape class via ``core.autotune``.  ``None`` disables
    lane blocking.  ``AUTO`` takes the backend's own default
    (``autotune.default_blk_b``).  Results are bit-identical for any
    value.

    max_banks: static bank-scoreboard bound of the contention model;
    ``None`` keeps the 16-slot default.  Configs with more banks than the
    bound hard-assert at call time -- eagerly when concrete, via a staged
    runtime callback when the caller jits the fn -- instead of silently
    aliasing.  ``sweep()`` derives the bound from its configs (and passes
    ``validate=False``, since its configs are pre-checked by
    construction), so prefer it for exotic topologies.

    reduce: an ``analysis.pareto`` reduction spec (``TopK`` /
    ``ParetoFront``).  Batch API only; the signature becomes
    ``fn(mem_init, hw, prog_idx, lane_idx) -> ReducedResult`` and the
    per-program segmented reduction runs on device (fused into the
    Pallas engine's compiled program; composed with the cached jitted
    reducer on the XLA path), so only ``O(G*K)`` candidate values ever
    reach the host.  ``lane_idx`` carries each lane's original flat grid
    index; ``-1`` marks padded lanes, which are masked with +inf
    sentinels and can never become candidates.
    """
    if max_banks is None:
        max_banks = DEFAULT_MAX_BANKS
    if is_auto(blk_b):
        blk_b = default_blk_b(backend)
    if reduce is not None and isinstance(program, Program):
        raise ValueError("reduce= needs the batch API; pass a sequence "
                         "of programs or a ProgramBatch")
    if backend == "pallas":
        from ..kernels.cgra_sweep.ops import make_pallas_sweep_fn
        return make_pallas_sweep_fn(
            program, profile, rows=rows, cols=cols, mem_size=mem_size,
            max_steps=max_steps, chunk_steps=chunk_steps, blk_b=blk_b,
            interpret=interpret, max_banks=max_banks, validate=validate,
            reduce=reduce)
    if backend != "xla":
        raise ValueError(f"unknown sweep backend: {backend!r}")

    chunk = _norm_chunk(chunk_steps, max_steps)
    if isinstance(program, Program):
        # single-program API: seed-style constant-closure fast path
        vfn = _xla_single_sweep_fn(program, profile, rows, cols, mem_size,
                                   max_steps, chunk, max_banks)

        def fn(mem_init, hw: HwConfig) -> SweepResult:
            if validate:
                validate_bank_bound(hw.n_banks, max_banks,
                                    where="dse.make_sweep_fn(backend='xla')")
            return vfn(mem_init, hw)
    else:
        batch = as_program_batch(program)
        fused = jnp.asarray(fused_rows(batch_tables(batch)))  # (G*T, NF, P)
        plen = jnp.asarray(batch.n_instrs, jnp.int32)         # (G,)
        tbl = _profile_tables(profile)
        core = _xla_sweep_core(rows, cols, mem_size, max_steps, chunk,
                               max_banks, batch.t_max)

        def fn(mem_init, hw: HwConfig, prog_idx) -> SweepResult:
            if validate:
                validate_bank_bound(hw.n_banks, max_banks,
                                    where="dse.make_sweep_fn(backend='xla')")
            gi = jnp.asarray(prog_idx, jnp.int32)
            B = int(mem_init.shape[0])
            # Lane-blocked dispatch: big packed batches spill the
            # per-lane state (mem image + registers) out of cache, so
            # the cached executable is driven over <= blk_b-lane blocks
            # and the results concatenated -- bit-identical (lanes are
            # independent) and still one trace (every block has the
            # same padded shape).  Skipped under an outer jit/shard_map
            # (mesh path): blocking is a dispatch-level optimization
            # and python-slicing a sharded operand would just reshard.
            if (blk_b is None or B <= blk_b
                    or isinstance(mem_init, jax.core.Tracer)):
                return core(fused, plen, tbl, mem_init, hw, gi)
            nblk = -(-B // blk_b)
            bs = -(-B // nblk)
            pad = nblk * bs - B

            def padlanes(x):
                x = jnp.asarray(x)
                if pad == 0:
                    return x
                return jnp.concatenate(
                    [x, jnp.repeat(x[:1], pad, axis=0)], axis=0)

            mem_p = padlanes(mem_init)
            hw_p = jax.tree.map(padlanes, hw)
            gi_p = padlanes(gi)
            parts = [core(fused, plen, tbl,
                          mem_p[i * bs:(i + 1) * bs],
                          jax.tree.map(lambda x: x[i * bs:(i + 1) * bs],
                                       hw_p),
                          gi_p[i * bs:(i + 1) * bs])
                     for i in range(nblk)]
            out = jax.tree.map(lambda *xs: jnp.concatenate(xs, axis=0),
                               *parts)
            return jax.tree.map(lambda x: x[:B], out)

    if reduce is not None:
        # Compose the cached jitted segmented reducer over the core's
        # device-resident output: the (B,) arrays flow device-to-device
        # into the reduction and only the (G, K) candidate set is ever
        # fetched by callers.
        red = _pareto.make_device_reducer(reduce, batch.n_programs)
        base = fn

        def rfn(mem_init, hw: HwConfig, prog_idx, lane_idx):
            res = base(mem_init, hw, prog_idx)
            return red(tuple(res), jnp.asarray(prog_idx, jnp.int32),
                       jnp.asarray(lane_idx, jnp.int32))

        return rfn

    return fn


class GridPlan(NamedTuple):
    """The flattened (program x hardware x data) grid as *data*: packed
    program batch, the D distinct images, and per-lane index/config rows.
    Index arrays live on the host (numpy) so any contiguous slice of
    lanes -- a work unit of the resumable sweep runner
    (``service.runner``) -- is a cheap row slice, never a re-plan."""
    batch: ProgramBatch
    images: jnp.ndarray        # (D, M) int32, device-resident once
    img_idx: np.ndarray        # (B,) int32 per-lane image row
    prog_idx: np.ndarray       # (B,) int32 per-lane program row
    hw_grid: HwConfig          # batched leaves, (B,) each
    max_banks: int             # config-derived scoreboard bound

    @property
    def n_lanes(self) -> int:
        return int(self.img_idx.shape[0])


def plan_grid(program: Union[Program, ProgramBatch, Sequence[Program], None]
              = None, hw_configs: Sequence[HwConfig] = None,
              mem_images: np.ndarray = None, *,
              programs: Optional[Sequence[Program]] = None) -> GridPlan:
    """Flatten the (program x hw x data) grid to ``B = G*H*D`` index rows
    (row ``(g*H + h)*D + d``) without materializing any tiled images or
    tables.  ``sweep()`` consumes the whole plan in one call; the sweep
    service slices it into checkpointable work units."""
    if programs is not None:
        if program is not None:
            raise TypeError("plan_grid(): pass either program or "
                            "programs=, not both")
        program = list(programs)
    batch = as_program_batch(program)
    G = batch.n_programs
    H, D = len(hw_configs), mem_images.shape[0]
    n_banks_req = max(int(np.asarray(c.n_banks)) for c in hw_configs)
    max_banks = scoreboard_bound(max(n_banks_req, DEFAULT_MAX_BANKS))
    hw_b = stack_configs(list(hw_configs))
    # broadcast to the full flat grid: hw h repeats over the data axis,
    # then the (hw x data) block tiles over the program axis
    hw_grid = jax.tree.map(
        lambda x: jnp.tile(jnp.repeat(x, D, axis=0), G), hw_b)
    images = jnp.asarray(mem_images, jnp.int32)          # (D, M), one copy
    img_idx = np.tile(np.arange(D, dtype=np.int32), G * H)      # (G*H*D,)
    prog_idx = np.repeat(np.arange(G, dtype=np.int32), H * D)
    return GridPlan(batch, images, img_idx, prog_idx, hw_grid, max_banks)


def _shard_call(fn, images, mesh, reduce=None):
    """THE mesh path of every sweep, both backends, reduced or not.

    Each device runs ``fn`` (the XLA operand core or the Pallas engine)
    under ``jax.shard_map`` on its shard of the flat grid, gathering its
    memory images by index from the replicated ``(D, M)`` stack.  The
    returned ``call(idx, hw, gi[, lane])`` takes lane rows whose length
    divides the device count.  Unreduced, the result stays sharded over
    the mesh.  Reduced, every device reduces its shard to a ``(G, K)``
    candidate set on device (padded lanes carry ``lane_idx = -1``) and
    only the gathered ``n_devices * G * K`` candidates reach the host,
    where ``merge_reduced`` recovers exactly the monolithic answer."""
    from jax.sharding import PartitionSpec

    from ..parallel.sharding import flat_batch_spec
    flat = flat_batch_spec(mesh)
    n_dev = int(mesh.devices.size)

    def shard_fn(imgs, idx, gi, hw, *lane):
        out = fn(jnp.take(imgs, idx, axis=0), hw, gi, *lane)
        return out if reduce is None else jax.tree.map(lambda x: x[None],
                                                        out)

    n_lane_args = 3 if reduce is None else 4
    sharded = jax.jit(jax.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(PartitionSpec(),) + (flat,) * n_lane_args,
        out_specs=flat, check_vma=False))

    def call(idx, hw, gi, *lane):
        out = sharded(images, jnp.asarray(idx, jnp.int32), jnp.asarray(
            gi, jnp.int32), hw, *(jnp.asarray(x, jnp.int32) for x in lane))
        if reduce is None:
            return out
        stacked = [np.asarray(leaf) for leaf in out]
        parts = [_pareto.ReducedResult(*(leaf[i] for leaf in stacked))
                 for i in range(n_dev)]
        return _pareto.merge_reduced(reduce, parts)

    call.sharded = sharded        # the SPMD program, for AOT compilation
    return call


def make_grid_fn(plan: GridPlan, profile: Profile, *,
                 max_steps: int = 2048, mem_size: int = 4096,
                 backend: str = "xla", chunk_steps: Optional[int] = 64,
                 blk_b: Union[int, None, str] = AUTO,
                 interpret: Optional[bool] = None,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 reduce: Optional[_pareto.Reduction] = None):
    """Unit-sliceable sweep core: ``fn(img_idx, hw_slice, prog_idx) ->
    SweepResult`` for ANY contiguous (or gathered) slice of the planned
    grid.  The underlying executable is the lru-cached operand core, so
    every same-length slice -- every work unit of a partitioned sweep --
    reuses one compiled program per backend (zero retrace), and a lane's
    result is bit-identical whether it runs in a monolithic sweep or
    inside any unit partition (lanes are independent).

    With ``mesh`` the slice runs SPMD over its devices through the same
    ``shard_map`` builder as ``sweep``; slice lengths must then divide
    the device count -- the sweep runner pads its units accordingly.

    With ``reduce`` the signature gains a trailing ``lane_idx`` row
    (original flat grid index per lane, -1 for padded lanes) and the fn
    returns the unit's ``ReducedResult`` -- per-program candidates
    reduced on device (per shard on a mesh, merged from the gathered
    ``n_devices*K`` candidates on host), so a checkpointable work unit
    ships O(G*K) bytes instead of its lane count."""
    fn = make_sweep_fn(plan.batch, profile, max_steps=max_steps,
                       mem_size=mem_size, backend=backend,
                       chunk_steps=chunk_steps, blk_b=blk_b,
                       interpret=interpret, max_banks=plan.max_banks,
                       validate=False, reduce=reduce)
    images = plan.images
    if mesh is None:
        def grid_fn(idx, hw, gi, *lane):
            return fn(jnp.take(images, jnp.asarray(idx, jnp.int32), axis=0),
                      hw, jnp.asarray(gi, jnp.int32),
                      *(jnp.asarray(x, jnp.int32) for x in lane))
        return grid_fn

    return _shard_call(fn, images, mesh, reduce)


def sweep(program: Union[Program, ProgramBatch, Sequence[Program], None]
          = None, profile: Profile = None,
          hw_configs: Sequence[HwConfig] = None,
          mem_images: np.ndarray = None, *,
          programs: Optional[Sequence[Program]] = None,
          mesh: Optional[jax.sharding.Mesh] = None,
          max_steps: int = 2048, mem_size: int = 4096,
          backend: str = "xla",
          chunk_steps: Union[int, None, str] = AUTO,
          blk_b: Union[int, str] = AUTO,
          max_buckets: Union[int, str] = AUTO,
          autotune: Optional[bool] = None,
          interpret: Optional[bool] = None,
          reduce: Optional[_pareto.Reduction] = None,
          observed_steps: Optional[Sequence[int]] = None,
          mappings: Optional[MappingSet] = None,
          fold_mappings: bool = True
          ) -> Union[SweepResult, _pareto.ReducedResult]:
    """Run the full (program x hw x data) grid through the lru-cached
    operand core(s), optionally sharded over every device of a mesh.

    program/programs: a single ``Program``, a sequence of programs, or a
    prebuilt ``ProgramBatch`` (``programs=`` is a keyword alias for call
    sites that sweep many kernels).  mem_images: (D, mem_size).  The
    grid is flattened to ``B = G*H*D``, row ``(g*H + h)*D + d`` pairing
    programs[g] with hw_configs[h] and mem_images[d]; a single program
    keeps the legacy ``h*D + d`` layout (G=1).

    The grid is broadcast *by index* on both the data and program axes:
    the D distinct memory images and the fused ``(G*T_max, N_ROW_FIELDS,
    P)`` row table go to the device(s) once, and each design point
    gathers its image and (one row per step, at ``prog_idx * T_max +
    pc``) its kernel's instructions inside the jitted program -- the
    host never materializes tiled copies.  The unsharded multi-program
    path calls the cached operand core *eagerly* (no per-call grid
    wrapper to re-jit), so repeated sweeps of any same-padded-shape
    kernel set are steady-state: zero compiles, zero retraces
    (``TRACE_COUNTS``).

    chunk_steps / blk_b / max_buckets default to ``autotune.AUTO``: they
    resolve through the per-shape-class autotune cache
    (``core.autotune``), falling back to the static defaults (64 / 128 /
    4) when the shape was never tuned.  Pass concrete values to pin
    knobs (``chunk_steps=None`` still means "disable chunking").  With
    ``autotune=True`` (or ``REPRO_AUTOTUNE=1``) an untuned multi-program
    shape is timed across a small candidate grid first and the winner is
    persisted for every later call of that shape.  ``backend=AUTO``
    makes the engine choice itself a tuned knob: an explicit backend
    always wins, a cached xla-vs-pallas winner for this shape class is
    used next, and with tuning opted in an unseen shape times both
    engines once (``tune_sweep(backend=AUTO)``); otherwise ``"xla"``.

    max_buckets > 1 splits a multi-kernel sweep into up to that many
    length buckets (``program.bucket_programs``): each bucket packs to
    its own (smaller) ``t_max`` and runs through its own cached core, so
    short kernels stop convoying behind the longest kernel of the whole
    set.  Results are scattered back to the canonical ``(g*H + h)*D + d``
    row order and are bit-identical to the unbucketed sweep; compiled
    cores grow by at most the number of buckets, not G.

    Mesh sharding works the same way for both backends (``_shard_call``):
    under ``jax.shard_map`` each device sweeps its shard of the flat
    grid through its own engine with an independent early-exit loop.
    Results are identical on 1 and N devices and stay sharded over the
    mesh; a grid that does not divide the device count is padded with
    duplicate lanes and sliced back.

    The bank-scoreboard bound of the contention model is derived here from
    the configs (padded to a power of two); configs beyond the hard
    ceiling fail with an assertion instead of silently aliasing.

    reduce: an ``analysis.pareto`` spec (``TopK(objective, k)`` /
    ``ParetoFront(axes, max_points)``).  The per-program reduction runs
    on device inside the compiled sweep -- per bucket when bucketed, per
    device on a mesh -- and only the ``O(G*K)`` candidate sets are
    merged on the host (``merge_reduced``), so the ``(B,)`` grid never
    leaves the device.  Returns a host-resident ``ReducedResult`` whose
    candidates are tagged with their canonical flat grid index
    ``(g*H + h)*D + d``; results are bit-identical to reducing the
    unreduced sweep with the numpy oracle, for any bucketing, mesh, or
    backend.

    observed_steps: optional per-program observed ``steps_executed``
    maxima from a prior run; when given, length bucketing groups by
    *trip count* instead of static program length
    (``program.bucket_programs(observed_steps=...)``), which separates
    kernels whose runtimes diverge from their instruction counts.

    mappings: a ``program.MappingSet`` -- mapping as a batched axis.
    The K candidate schedules per kernel flatten onto the ordinary
    program axis (B = K_total * H * D, per-lane ``prog_idx``, same
    bucketing / retrace guarantees), so a candidate set costs one
    compile per bucket, not one per mapping.  Without ``reduce`` the
    full per-candidate lanes come back.  With ``reduce`` the
    per-candidate rows are folded through the set's ``(kernel_id,
    mapping_id)`` segment map (``analysis.pareto.fold_segments``) and
    only each *kernel's* best-mapping front crosses to the caller --
    candidate flat indices stay in candidate-lane coordinates, so the
    winning mapping id is ``mappings.mapping_of[idx // (H*D)]``.  Pass
    ``fold_mappings=False`` to keep per-candidate reduced rows.
    """
    if mappings is not None:
        if program is not None or programs is not None:
            raise TypeError(
                "sweep: pass mappings= OR program(s)=, not both")
        res = sweep(programs=list(mappings.programs), profile=profile,
                    hw_configs=hw_configs, mem_images=mem_images,
                    mesh=mesh, max_steps=max_steps, mem_size=mem_size,
                    backend=backend, chunk_steps=chunk_steps, blk_b=blk_b,
                    max_buckets=max_buckets, autotune=autotune,
                    interpret=interpret, reduce=reduce,
                    observed_steps=observed_steps)
        if reduce is not None and fold_mappings:
            return _pareto.fold_segments(reduce, res, mappings.kernel_of,
                                         mappings.n_kernels)
        return res
    plan = plan_grid(program, hw_configs, mem_images, programs=programs)
    batch = plan.batch
    G = batch.n_programs
    H, D = len(hw_configs), mem_images.shape[0]
    n_dev = int(mesh.devices.size) if mesh is not None else 1

    cache = default_cache()
    if is_auto(backend):
        # backend itself is a tuned knob: explicit > cached winner >
        # (with tuning opted in) time xla-vs-pallas now > default xla
        auto_shape = ShapeClass(G=G, t_max=batch.t_max, H=H, D=D,
                                backend=AUTO, n_devices=n_dev)
        cached_b = cache.lookup(auto_shape)
        if cached_b is not None and cached_b.backend in ("xla", "pallas"):
            backend = cached_b.backend
        elif autotune_enabled(autotune) and G > 1:
            cfg_b = tune_sweep(batch, profile, hw_configs, mem_images,
                               backend=AUTO, max_steps=max_steps,
                               mem_size=mem_size, mesh=mesh,
                               interpret=interpret, cache=cache)
            backend = cfg_b.backend or "xla"
        else:
            backend = "xla"

    shape = ShapeClass(G=G, t_max=batch.t_max, H=H, D=D, backend=backend,
                       n_devices=n_dev)
    cfg = cache.resolve(shape, blk_b=blk_b, chunk_steps=chunk_steps,
                        max_buckets=max_buckets)
    if (autotune_enabled(autotune) and cfg.source == "default" and G > 1
            and is_auto(blk_b, chunk_steps, max_buckets)):
        # first encounter of an untuned shape with tuning opted in: time
        # the candidate grid once, persist, and run with the winner
        cfg = tune_sweep(batch, profile, hw_configs, mem_images,
                         backend=backend, max_steps=max_steps,
                         mem_size=mem_size, mesh=mesh, interpret=interpret,
                         cache=cache)

    if G > 1 and cfg.max_buckets > 1:
        buckets = bucket_programs([batch.program(g) for g in range(G)],
                                  cfg.max_buckets,
                                  observed_steps=observed_steps)
        if buckets.n_buckets > 1:
            block = H * D
            # Forward the caller's original chunk/blk knobs (AUTO or
            # explicit), not the resolved top-level values: each bucket
            # is its own shape class (G=n_b, its own t_max), so an AUTO
            # knob picks up that bucket's tuned winner -- a short-kernel
            # bucket can run a smaller chunk_steps than a long one.
            parts = [
                sweep(program=b, profile=profile, hw_configs=hw_configs,
                      mem_images=mem_images, mesh=mesh, max_steps=max_steps,
                      mem_size=mem_size, backend=backend,
                      chunk_steps=chunk_steps, blk_b=blk_b,
                      max_buckets=1, autotune=False, interpret=interpret,
                      reduce=reduce)
                for b in buckets.batches]

            if reduce is not None:
                # Each bucket reduced itself on device; lift its rows
                # into the global segment space (bucket-local program j
                # maps to canonical program g, shifting candidate flat
                # indices by the row-block offset) and merge the K-sized
                # candidate sets -- never B-sized grids -- on the host.
                placed = [
                    _pareto.remap_segments(
                        part, buckets.groups[bi],
                        [(g - j) * block
                         for j, g in enumerate(buckets.groups[bi])], G)
                    for bi, part in enumerate(parts)]
                return _pareto.merge_reduced(reduce, placed)

            def scatter(*leaves):
                out = None
                for bi, leaf in enumerate(leaves):
                    a = np.asarray(leaf)
                    if out is None:
                        out = np.empty((G * block,) + a.shape[1:], a.dtype)
                    for j, g in enumerate(buckets.groups[bi]):
                        out[g * block:(g + 1) * block] = \
                            a[j * block:(j + 1) * block]
                return jnp.asarray(out)

            return jax.tree.map(scatter, *parts)

    images = plan.images
    img_idx = jnp.asarray(plan.img_idx)
    prog_idx = jnp.asarray(plan.prog_idx)
    hw_grid = plan.hw_grid
    # validate=False: every config was checked against the plan's derived
    # scoreboard bound, so no runtime guard needs to be staged into the
    # compiled sweep
    kw = dict(max_steps=max_steps, mem_size=mem_size, backend=backend,
              chunk_steps=cfg.chunk_steps, blk_b=cfg.blk_b,
              interpret=interpret, max_banks=plan.max_banks, validate=False)
    # The constant-closure fast path is reserved for callers that hand us
    # a bare Program (the legacy single-kernel API).  A 1-element batch
    # or list goes through the operand core instead, so single-program
    # buckets of a bucketed sweep share the cached executables.  A
    # reduced sweep always uses the operand core (the reducer keys its
    # segments on the prog_idx operand).
    single_const = (programs is None and isinstance(program, Program)
                    and reduce is None)
    if single_const:
        fn1 = make_sweep_fn(program, profile, **kw)
        fn = lambda mem, hw, gi: fn1(mem, hw)
    else:
        fn = make_sweep_fn(batch, profile, **kw, reduce=reduce)

    if mesh is None:
        if reduce is not None:
            lane_idx = jnp.arange(G * H * D, dtype=jnp.int32)
            red = fn(jnp.take(images, img_idx, axis=0), hw_grid, prog_idx,
                     lane_idx)
            return _pareto.merge_reduced(reduce, [red])
        if single_const:
            # legacy data flow: the constant-closure vfn is unjitted by
            # design (tables fold into the executable); jit the wrapper
            return jax.jit(lambda idx, hw, gi: fn(
                jnp.take(images, idx, axis=0), hw, gi))(
                    img_idx, hw_grid, prog_idx)
        # operand core: already jitted + lru-cached, so call it eagerly
        # -- a per-call jit wrapper here would recompile the whole
        # pipeline every sweep() call and forfeit the steady state
        return fn(jnp.take(images, img_idx, axis=0), hw_grid, prog_idx)

    from ..parallel.sharding import pad_batch, padded_len
    # The mesh path needs the flat grid divisible by the device count;
    # pad with duplicate (harmless, independent) lanes and slice back.
    B = G * H * D
    Bp = padded_len(B, int(mesh.devices.size))
    img_idx = pad_batch(img_idx, Bp)
    prog_idx = pad_batch(prog_idx, Bp)
    hw_grid = jax.tree.map(lambda x: pad_batch(x, Bp), hw_grid)
    call = _shard_call(fn, images, mesh, reduce)
    if reduce is not None:
        # duplicate pad lanes are masked via lane_idx = -1
        lane_idx = pad_batch(jnp.arange(B, dtype=jnp.int32), Bp, fill=-1)
        return call(img_idx, hw_grid, prog_idx, lane_idx)
    res = call(img_idx, hw_grid, prog_idx)
    if Bp == B:
        return res                     # stays sharded over the mesh
    # a padded grid no longer splits evenly: replicate, then slice
    from ..parallel.sharding import replicated_sharding
    rep = replicated_sharding(mesh)
    return jax.tree.map(lambda x: jax.device_put(x, rep)[:B], res)


def make_bucketed_sweep_fn(programs, profile: Profile,
                           hw_configs: Sequence[HwConfig],
                           mem_images: np.ndarray, *,
                           max_steps: int = 2048, mem_size: int = 4096,
                           backend: str = "xla",
                           chunk_steps: Union[int, None, str] = AUTO,
                           blk_b: Union[int, str] = AUTO,
                           max_buckets: Union[int, str] = AUTO,
                           interpret: Optional[bool] = None,
                           reduce: Optional[_pareto.Reduction] = None,
                           observed_steps: Optional[Sequence[int]] = None):
    """Hold a bucketed packed plan: ``fn() -> SweepResult``.

    ``sweep()`` re-packs, re-buckets, and re-resolves knobs on every
    call -- fine for one-shot grids, pure overhead for a steady-state
    loop (a service slot, a benchmark) that re-executes the *same*
    kernel set.  This builds everything once -- length buckets, per-
    bucket autotune-resolved knobs, per-bucket operand fns, device-
    resident lane operands -- and returns a zero-argument callable that
    executes the buckets and scatters lanes back to canonical
    ``(g*H + h)*D + d`` order, bit-identical to ``sweep()``.

    The returned fn exposes the plan for introspection: ``fn.buckets``
    (``ProgramBuckets``), ``fn.bucket_fns`` (list of ``(sweep_fn, mems,
    hw, prog_idx)`` operand tuples), ``fn.bucket_cfgs`` (per-bucket
    ``TunedConfig``).  Unsharded only (a mesh shards *within* one
    ``sweep`` call; hold one plan per mesh instead).

    With ``reduce`` each bucket reduces itself on device (the lane
    operands carry *canonical* flat grid indices, precomputed here once)
    and ``fn() -> ReducedResult`` merges the K-sized per-bucket
    candidate sets on the host -- the steady-state loop never touches a
    ``(B,)`` array.  ``observed_steps`` buckets by trip count instead of
    static length (see ``program.bucket_programs``)."""
    batch = as_program_batch(programs)
    G = batch.n_programs
    H, D = len(hw_configs), int(mem_images.shape[0])
    cache = default_cache()
    top = cache.resolve(
        ShapeClass(G=G, t_max=batch.t_max, H=H, D=D, backend=backend),
        blk_b=blk_b, chunk_steps=chunk_steps, max_buckets=max_buckets)
    buckets = bucket_programs([batch.program(g) for g in range(G)],
                              top.max_buckets if G > 1 else 1,
                              observed_steps=observed_steps)
    block = H * D
    bucket_fns, bucket_cfgs, bucket_lanes = [], [], []
    for bi, b in enumerate(buckets.batches):
        plan = plan_grid(b, hw_configs, mem_images)
        cfgb = cache.resolve(
            ShapeClass(G=b.n_programs, t_max=b.t_max, H=H, D=D,
                       backend=backend),
            blk_b=blk_b, chunk_steps=chunk_steps, max_buckets=1)
        fnb = make_sweep_fn(b, profile, mem_size=mem_size,
                            max_steps=max_steps, backend=backend,
                            chunk_steps=cfgb.chunk_steps, blk_b=cfgb.blk_b,
                            interpret=interpret, max_banks=plan.max_banks,
                            validate=False, reduce=reduce)
        mems = jnp.take(plan.images, jnp.asarray(plan.img_idx), axis=0)
        bucket_fns.append((fnb, mems, plan.hw_grid,
                           jnp.asarray(plan.prog_idx)))
        bucket_cfgs.append(cfgb)
        if reduce is not None:
            # canonical flat indices of this bucket's lanes, so bucket
            # candidates come back already tagged in global coordinates
            bucket_lanes.append(jnp.asarray(np.concatenate(
                [np.arange(g * block, (g + 1) * block, dtype=np.int32)
                 for g in buckets.groups[bi]])))

    if reduce is not None:
        def fn() -> _pareto.ReducedResult:
            placed = [
                _pareto.remap_segments(
                    f(m, h, gi, bucket_lanes[bi]), buckets.groups[bi],
                    np.zeros(len(buckets.groups[bi]), np.int64), G)
                for bi, (f, m, h, gi) in enumerate(bucket_fns)]
            return _pareto.merge_reduced(reduce, placed)
    else:
        def fn() -> SweepResult:
            parts = [f(m, h, gi) for f, m, h, gi in bucket_fns]

            def scatter(*leaves):
                out = None
                for bi, leaf in enumerate(leaves):
                    a = np.asarray(leaf)
                    if out is None:
                        out = np.empty((G * block,) + a.shape[1:], a.dtype)
                    for j, g in enumerate(buckets.groups[bi]):
                        out[g * block:(g + 1) * block] = \
                            a[j * block:(j + 1) * block]
                return jnp.asarray(out)

            return jax.tree.map(scatter, *parts)

    fn.buckets = buckets
    fn.bucket_fns = bucket_fns
    fn.bucket_cfgs = bucket_cfgs
    fn.reduce = reduce
    return fn


# ---------------------------------------------------------------------------
# Mapping search: the simulator as the inner loop of an optimizer
# ---------------------------------------------------------------------------

class MappingSearchResult(NamedTuple):
    """Outcome of ``search_mappings``.

    best / best_policy / best_score: per-kernel winner across every
    round (score is the search objective at the winner's best (hw,
    data) lane -- lower is better).  front: the final candidate set
    reduced per kernel on device (each kernel's best-mapping front).
    mappings: the final-round ``MappingSet`` (front rows index into
    it).  history: one dict per round with per-kernel best/worst scores
    and the candidate counts actually scored.
    """
    best: list
    best_policy: list
    best_score: np.ndarray
    front: _pareto.ReducedResult
    mappings: MappingSet
    history: list


def _candidate_scores(objective: str,
                      red: _pareto.ReducedResult) -> np.ndarray:
    """(n_rows,) objective value of each row's best lane (top-1 rows)."""
    fields = [np.asarray(getattr(red, f))[:, 0]
              for f in _pareto.RESULT_FIELDS]
    vals = _pareto.objective_values(objective, fields)
    return np.where(np.asarray(red.count) > 0, vals, np.inf)


def search_mappings(dags: Sequence, profile: Profile,
                    hw_configs: Sequence[HwConfig],
                    mem_images: np.ndarray, *,
                    k: int = 8, keep: int = 2, rounds: int = 2,
                    seed: int = 0, objective: str = "edp",
                    names: Optional[Sequence[str]] = None,
                    rows: int = 4, cols: int = 4,
                    max_steps: int = 2048, mem_size: int = 4096,
                    backend: str = "xla",
                    chunk_steps: Union[int, None, str] = AUTO,
                    blk_b: Union[int, str] = AUTO,
                    max_buckets: Union[int, str] = AUTO,
                    interpret: Optional[bool] = None,
                    reduce: Optional[_pareto.Reduction] = None
                    ) -> MappingSearchResult:
    """Greedy mapping refinement: sweep K candidates -> keep top-M ->
    mutate -> re-sweep.  Closes the ROADMAP "close the loop" item: the
    batched simulator is the inner loop of a schedule optimizer.

    Per round, every kernel's candidate set (``mapper.generate_
    candidates``: survivors' policies first, then seeded mutations of
    them, then fresh shuffled policies; all deduped and verified against
    ``DAG.evaluate``) is flattened into one ``MappingSet`` and scored
    against the full (hw x data) grid by ONE held bucketed plan
    (``make_bucketed_sweep_fn`` with an on-device top-1 reduction per
    candidate) -- K·H·D design points per round for at most n_buckets
    compiles, and later rounds with same-shape candidate sets hit the
    lru-cached cores outright.  The per-kernel ``keep`` best (by
    ``objective`` at each candidate's best lane) survive to seed the
    next round; the best candidate ever seen is tracked across rounds.

    Returns a :class:`MappingSearchResult`; ``front`` reduces the final
    candidate set per kernel on device with ``reduce`` (default
    ``TopK(objective, keep)``), exactly what ``sweep(mappings=...)``
    ships back for a production-size search.
    """
    from .mapper import generate_candidates, mutate_policy

    if keep < 1 or k < keep:
        raise ValueError(f"need 1 <= keep <= k, got keep={keep} k={k}")
    names = (list(names) if names is not None
             else [f"kernel{g}" for g in range(len(dags))])
    if len(names) != len(dags):
        raise ValueError(f"{len(names)} names for {len(dags)} DAGs")
    n_kernels = len(dags)
    top1 = _pareto.TopK(objective, k=1)
    H, D = len(hw_configs), int(mem_images.shape[0])

    survivors = [None] * n_kernels      # per kernel: list[MappingCandidate]
    best = [None] * n_kernels           # per kernel: (score, candidate)
    history = []
    mset = None
    for r in range(rounds):
        groups = []
        for g, dag in enumerate(dags):
            if r == 0:
                cands = generate_candidates(dag, k, seed=seed + 7 * g,
                                            rows=rows, cols=cols,
                                            name=names[g])
            else:
                rng = np.random.default_rng(
                    (seed + 1) * 9176 + 131 * r + g)
                pols = [c.policy for c in survivors[g]]
                while len(pols) < 3 * k:
                    parent = survivors[g][
                        int(rng.integers(0, len(survivors[g])))]
                    pols.append(mutate_policy(parent.policy, rng))
                cands = generate_candidates(dag, k, seed=seed,
                                            rows=rows, cols=cols,
                                            name=names[g], policies=pols)
            groups.append(cands)
        mset = MappingSet.from_candidates(
            [[c.program for c in grp] for grp in groups], names=names)
        plan_fn = make_bucketed_sweep_fn(
            list(mset.programs), profile, hw_configs, mem_images,
            max_steps=max_steps, mem_size=mem_size, backend=backend,
            chunk_steps=chunk_steps, blk_b=blk_b, max_buckets=max_buckets,
            interpret=interpret, reduce=top1)
        scores = _candidate_scores(objective, plan_fn())
        row = {"round": r, "n_candidates": [len(g) for g in groups],
               "best": [], "worst": []}
        offset = 0
        for g, grp in enumerate(groups):
            s = scores[offset:offset + len(grp)]
            offset += len(grp)
            order = np.argsort(s, kind="stable")
            survivors[g] = [grp[i] for i in order[:keep]]
            row["best"].append(float(s[order[0]]))
            row["worst"].append(float(s[order[-1]]))
            if best[g] is None or float(s[order[0]]) < best[g][0]:
                best[g] = (float(s[order[0]]), grp[order[0]])
        history.append(row)

    front = sweep(mappings=mset, profile=profile, hw_configs=hw_configs,
                  mem_images=mem_images, max_steps=max_steps,
                  mem_size=mem_size, backend=backend,
                  chunk_steps=chunk_steps, blk_b=blk_b,
                  max_buckets=max_buckets, interpret=interpret,
                  reduce=reduce or _pareto.TopK(objective, k=keep))
    return MappingSearchResult(
        best=[b[1].program for b in best],
        best_policy=[b[1].policy for b in best],
        best_score=np.asarray([b[0] for b in best], np.float64),
        front=front, mappings=mset, history=history)

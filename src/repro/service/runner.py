"""Fault-tolerant, resumable sweep runner.

A monolithic ``dse.sweep`` over a million-point (program x hw x data)
grid is all-or-nothing: one transient device error or SIGKILL loses the
whole campaign.  This runner makes large sweeps crash-safe without
giving up the zero-retrace hot path:

  * **Partitioned execution**: the flattened grid (``dse.plan_grid``) is
    split into fixed-size work units along the batch axis; every unit is
    padded to the same lane count, so ALL units of a campaign -- and all
    campaigns of the same shape -- share one compiled executable per
    backend (``dse.make_grid_fn`` over the lru-cached operand core).
  * **Checkpointed progress**: each completed unit's ``SweepResult``
    slice is persisted atomically via ``CheckpointManager`` (tmp-rename,
    so a crash mid-save never corrupts completed units).  A killed
    process resumes from the last complete unit and the stitched result
    is bit-identical to an uninterrupted run: lanes are independent, so
    a lane's numbers do not depend on which process computed its unit.
    Checkpoints carry a campaign fingerprint (grid + config hash);
    resuming against a different campaign's directory is refused.
  * **Retry / deadline / backoff + graceful degradation**: unit attempts
    are retried with exponential backoff; persistent failures degrade
    per-unit down a backend chain (``pallas`` -> ``pallas interpret`` ->
    ``xla``), recording which units degraded.
  * **Fleet wiring**: per-unit workers beat the ``HeartbeatBus``; a
    confirmed ``FailureDetector`` failure (or a persistent straggler's
    "replace" action) triggers an elastic re-plan that shrinks the
    device mesh for the remaining units -- completed units stay
    checkpointed, nothing re-runs.  ``StragglerDetector`` step times
    feed a unit-size rebalancing suggestion for the next campaign.
  * **Fault injection**: all of the above is exercised deterministically
    in CI via ``runtime.faults`` (no real hardware faults needed).

CLI (the subprocess target of the kill-and-resume tests)::

  PYTHONPATH=src python -m repro.service.runner \\
      --kernels bitcnt,crc32 --ckpt-dir /tmp/sweep_ck --unit-size 4 \\
      --out /tmp/sweep.npz
"""
from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import time
from pathlib import Path
from typing import Callable, Dict, List, Optional, Set, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from ..analysis import pareto as _pareto
from ..checkpoint import CheckpointManager
from ..checkpoint.manager import load_tree
from ..core import dse
from ..core.autotune import AUTO, ShapeClass, default_cache, is_auto, \
    resolve_backend
from ..core.characterization import Profile
from ..core.dse import GridPlan, SweepResult
from ..runtime import plan_downscale
from ..runtime.faults import BackendFault, FaultInjector
from .monitor import FleetMonitor

RESULT_FIELDS = tuple(SweepResult._fields)
_RESULT_DTYPES = {"latency_cc": np.int32, "energy_pj": np.float32,
                  "power_mw": np.float32, "checksum": np.int32,
                  "steps_executed": np.int32}


class SweepUnitError(RuntimeError):
    """A work unit failed on every backend of the degradation chain."""


class UnitTimeout(RuntimeError):
    """A unit attempt exceeded the per-unit deadline (retried)."""


class CheckpointMismatch(ValueError):
    """Checkpoint directory belongs to a different campaign (grid or
    config fingerprint differs) -- refusing to stitch foreign units."""


@dataclasses.dataclass(frozen=True)
class BackendStage:
    """One rung of the degradation chain."""
    name: str                   # "pallas" | "pallas_interpret" | "xla"
    backend: str                # dse backend selector
    interpret: Optional[bool]


def backend_chain(backend: str,
                  interpret: Optional[bool] = None
                  ) -> Tuple[BackendStage, ...]:
    """Degradation chain for a requested backend.

    On a TPU: compiled Pallas -> XLA scan.  The Pallas interpreter never
    stands in for the compiled kernel there: a unit that falls back is
    recorded as degraded to ``xla``, and ``interpret=True`` is refused.
    Off-TPU, where the engine only runs interpreted, the chain is
    ``pallas`` (auto-interpreted) -> ``pallas_interpret`` -> ``xla``;
    ``interpret=True`` starts it at the interpreter stage.  ``xla`` has
    nowhere slower-but-safer to go."""
    if backend == "xla":
        return (BackendStage("xla", "xla", None),)
    if backend != "pallas":
        raise ValueError(f"unknown sweep backend: {backend!r}")
    on_tpu = jax.default_backend() == "tpu"
    if on_tpu and interpret:
        raise ValueError("interpret=True: the Pallas interpreter is not a "
                         "TPU sweep backend")
    stages = []
    if interpret is not True:
        stages.append(BackendStage("pallas", "pallas", interpret))
    if not on_tpu:
        stages.append(BackendStage("pallas_interpret", "pallas", True))
    stages.append(BackendStage("xla", "xla", None))
    return tuple(stages)


@dataclasses.dataclass(frozen=True)
class RetryPolicy:
    """Per-unit retry/deadline/degradation policy."""
    max_attempts: int = 3            # attempts per backend stage
    backoff_s: float = 0.05          # first retry delay
    backoff_mult: float = 2.0        # exponential growth
    unit_timeout_s: Optional[float] = None   # post-hoc deadline per attempt
    degrade: bool = True             # walk the backend chain on exhaustion


@dataclasses.dataclass
class UnitRecord:
    unit: int
    lo: int
    hi: int
    backend: str          # stage name that produced the result
    attempts: int
    resumed: bool
    seconds: float
    node: str


@dataclasses.dataclass
class RunnerReport:
    """What happened to a campaign -- the service's observability."""
    units_total: int = 0
    units_run: int = 0
    units_resumed: int = 0
    units_skipped: int = 0
    attempts_total: int = 0
    degraded: Dict[int, str] = dataclasses.field(default_factory=dict)
    replans: List[dict] = dataclasses.field(default_factory=list)
    straggler_actions: List[dict] = dataclasses.field(default_factory=list)
    suggested_unit_size: Optional[int] = None
    wall_s: float = 0.0
    records: List[UnitRecord] = dataclasses.field(default_factory=list)

    def to_dict(self) -> dict:
        d = dataclasses.asdict(self)
        d["degraded"] = {str(k): v for k, v in self.degraded.items()}
        return d


class ResumableSweepRunner:
    """Partitioned, checkpointed, retry/degrade execution of one grid.

    Construct from raw grid axes (``programs``/``hw_configs``/
    ``mem_images``) or from a prebuilt ``plan`` (the sweep server packs
    several requests into one plan).  ``run()`` executes every pending
    unit and returns the stitched ``SweepResult`` plus a report; the
    server instead drives ``run_unit`` one unit at a time.

    With ``reduce`` (an ``analysis.pareto`` spec) every unit reduces on
    device and checkpoints its compacted ``(G, K)`` candidate set --
    kilobytes per unit instead of the lane slice -- and ``stitch``
    merges the unit fronts associatively (``merge_reduced``) into the
    campaign's ``ReducedResult``.  A resumed campaign merges to the
    bit-identical answer: units are reduced deterministically and the
    merge does not care which process produced a unit.  The reduction
    spec is part of the campaign fingerprint, so a checkpoint directory
    cannot mix reduced and unreduced (or differently-reduced) units.
    """

    def __init__(self, program=None, profile: Profile = None,
                 hw_configs=None, mem_images=None, *,
                 programs=None, mappings=None,
                 plan: Optional[GridPlan] = None,
                 ckpt_dir: Optional[str] = None, unit_size: int = 64,
                 max_steps: int = 2048, mem_size: int = 4096,
                 backend: str = "xla",
                 chunk_steps: Union[int, None, str] = AUTO,
                 blk_b: Union[int, str] = AUTO,
                 interpret: Optional[bool] = None,
                 reduce: Optional[_pareto.Reduction] = None,
                 mesh: Optional[jax.sharding.Mesh] = None,
                 retry: Optional[RetryPolicy] = None,
                 injector: Optional[FaultInjector] = None,
                 monitor: Optional[FleetMonitor] = None,
                 clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep,
                 on_unit=None, ckpt_async: bool = True):
        if mappings is not None:
            # mapping-search campaign: the candidate set flattens onto
            # the ordinary program axis (a MappingSet IS a program
            # sequence plus a segment map), so units, checkpoints, and
            # the fingerprint all work unchanged; ``stitch_folded``
            # collapses the reduced answer to per-kernel rows
            if program is not None or programs is not None:
                raise TypeError(
                    "ResumableSweepRunner: pass mappings= OR "
                    "program(s)=, not both")
            programs = list(mappings.programs)
        self.mappings = mappings
        if plan is None:
            plan = dse.plan_grid(program, hw_configs, mem_images,
                                 programs=programs)
        self.plan = plan
        self.profile = profile
        self.mesh = mesh
        self._initial_ndev = int(mesh.devices.size) if mesh is not None else 1
        # unit lanes must divide the device count for shard_map; padding
        # rounds the unit up, never down (checkpoint layout is in real
        # lane ranges, unaffected)
        self.unit_size = max(1, unit_size)
        self._padded_unit = -(-self.unit_size // self._initial_ndev) \
            * self._initial_ndev
        self.max_steps = max_steps
        self.mem_size = mem_size
        # AUTO knobs resolve through the per-shape autotune cache using
        # the service's lane-shape proxy (H = lanes per program, D = 1);
        # explicit values always win.  Resolution happens HERE so the
        # campaign fingerprint hashes concrete ints -- a checkpoint is
        # resumable regardless of later cache changes.  backend=AUTO
        # resolves the same way (cached xla-vs-pallas winner, else xla;
        # the runner never times candidates itself).
        G = plan.batch.n_programs
        lanes_per_prog = max(1, plan.n_lanes // max(G, 1))
        if is_auto(backend):
            backend = resolve_backend(ShapeClass(
                G=G, t_max=plan.batch.t_max, H=lanes_per_prog, D=1,
                backend=AUTO, n_devices=self._initial_ndev))
        self.backend = backend
        self.reduce = reduce
        self.G = G
        shape = ShapeClass(G=G, t_max=plan.batch.t_max,
                           H=lanes_per_prog, D=1,
                           backend=backend, n_devices=self._initial_ndev)
        cfg = default_cache().resolve(shape, blk_b=blk_b,
                                      chunk_steps=chunk_steps, max_buckets=1)
        self.chunk_steps = cfg.chunk_steps
        self.blk_b = cfg.blk_b
        self.tuned_source = cfg.source       # "explicit" | "cache" | "default"
        self.interpret = interpret
        self.retry = retry or RetryPolicy()
        self.injector = injector
        self.clock = clock
        self.sleep = sleep
        self.on_unit = on_unit
        self.ckpt_async = ckpt_async

        self.B = plan.n_lanes
        self.n_units = -(-self.B // self.unit_size)
        self._chain = backend_chain(backend, interpret)
        self._fns: Dict[Tuple[str, int], Callable] = {}
        self._mesh_epoch = 0
        self._results: Dict[int, Dict[str, np.ndarray]] = {}
        self._skipped: Set[int] = set()
        self._pending_replace: Set[str] = set()

        if monitor is None:
            nodes = [f"dev{i}" for i in range(self._initial_ndev)]
            monitor = FleetMonitor(nodes)
        self.monitor = monitor
        self._node_device = {}
        if mesh is not None:
            devs = list(np.asarray(mesh.devices).flat)
            self._node_device = dict(zip(self.monitor.nodes, devs))

        self.report = RunnerReport(units_total=self.n_units)
        self.fingerprint = self._fingerprint()
        self.mgr = None
        if ckpt_dir is not None:
            # keep_n=0: never expire unit checkpoints -- every unit is
            # needed to stitch the campaign
            self.mgr = CheckpointManager(ckpt_dir, keep_n=0)
            self._load_completed()

    # -- campaign identity --------------------------------------------------
    def _fingerprint(self) -> str:
        h = hashlib.sha256()
        b = self.plan.batch
        for a in (b.ops, b.dest, b.srcA, b.srcB, b.imm, b.n_instrs):
            h.update(np.ascontiguousarray(a).tobytes())
        for leaf in jax.tree.leaves(self.plan.hw_grid):
            h.update(np.asarray(leaf).tobytes())
        h.update(np.asarray(self.plan.images).tobytes())
        h.update(np.ascontiguousarray(self.plan.img_idx).tobytes())
        h.update(np.ascontiguousarray(self.plan.prog_idx).tobytes())
        h.update(json.dumps([self.max_steps, self.mem_size, self.unit_size,
                             self.chunk_steps, self.backend, self.blk_b,
                             _pareto.spec_to_str(self.reduce)
                             if self.reduce is not None else None]).encode())
        return h.hexdigest()

    # -- resume -------------------------------------------------------------
    def _load_completed(self):
        for step in self.mgr.steps():
            path = self.mgr.path(step)
            extra = json.loads(
                (path / "manifest.json").read_text()).get("extra", {})
            if extra.get("fingerprint") != self.fingerprint:
                raise CheckpointMismatch(
                    f"{path}: checkpoint fingerprint "
                    f"{extra.get('fingerprint', '?')[:12]} does not match "
                    f"this campaign ({self.fingerprint[:12]}); refusing to "
                    f"resume -- clear the directory or fix the grid/config")
            lo, hi = self._unit_range(step)
            if (int(extra.get("lo", -1)), int(extra.get("hi", -1))) \
                    != (lo, hi):
                raise CheckpointMismatch(
                    f"{path}: unit lane range {extra.get('lo')}:"
                    f"{extra.get('hi')} != planned {lo}:{hi}")
            if self.reduce is not None:
                like = _pareto.reduced_zeros(self.G, self.reduce)
            else:
                like = {f: np.zeros(hi - lo, _RESULT_DTYPES[f])
                        for f in RESULT_FIELDS}
            self._results[step] = load_tree(like, path)
            stage = extra.get("backend", self._chain[0].name)
            if stage != self._chain[0].name:
                self.report.degraded[step] = stage
            self.report.units_resumed += 1
            self.report.records.append(UnitRecord(
                unit=step, lo=lo, hi=hi, backend=stage,
                attempts=int(extra.get("attempts", 0)), resumed=True,
                seconds=0.0, node=""))

    def attach_checkpoints(self, ckpt_dir: Union[str, Path]) -> None:
        """Late-bind a checkpoint directory and load its completed units.

        The sweep service packs requests into a plan *before* it knows
        the campaign fingerprint, so it constructs the runner bare and
        attaches ``<ckpt_root>/<fingerprint prefix>`` afterwards: a
        re-submitted campaign (same grid, same config) resumes its
        completed units across a service restart, exactly like the
        ``ckpt_dir=`` constructor path."""
        if self._results or self._skipped:
            raise RuntimeError(
                "attach_checkpoints: campaign already has unit results; "
                "attach before the first run_unit call")
        self.mgr = CheckpointManager(str(ckpt_dir), keep_n=0)
        self._load_completed()

    # -- unit geometry ------------------------------------------------------
    def _unit_range(self, k: int) -> Tuple[int, int]:
        lo = k * self.unit_size
        return lo, min(self.B, lo + self.unit_size)

    def pending_units(self) -> List[int]:
        return [k for k in range(self.n_units)
                if k not in self._results and k not in self._skipped]

    def _unit_args(self, k: int):
        """Slice the plan for unit ``k``, padded to the common unit lane
        count with duplicates of the last real lane (independent lanes:
        redundant work, never wrong results).  Under ``reduce`` the
        returned lane row carries each lane's original flat grid index,
        -1 on the duplicate pad lanes so the reducer masks them (a
        repeated lane must not appear twice in a candidate set)."""
        lo, hi = self._unit_range(k)
        sel = np.minimum(np.arange(lo, lo + self._padded_unit), self.B - 1)
        idx = self.plan.img_idx[sel]
        gi = self.plan.prog_idx[sel]
        sel_j = jnp.asarray(sel)
        hw = jax.tree.map(lambda x: jnp.take(x, sel_j, axis=0),
                          self.plan.hw_grid)
        lane = None
        if self.reduce is not None:
            n = np.arange(self._padded_unit)
            lane = np.where(n < hi - lo, lo + n, -1).astype(np.int32)
        return idx, hw, gi, lane

    # -- executables --------------------------------------------------------
    def _fn_for(self, stage: BackendStage) -> Callable:
        key = (stage.name, self._mesh_epoch)
        fn = self._fns.get(key)
        if fn is None:
            fn = dse.make_grid_fn(
                self.plan, self.profile, max_steps=self.max_steps,
                mem_size=self.mem_size, backend=stage.backend,
                chunk_steps=self.chunk_steps, blk_b=self.blk_b,
                interpret=stage.interpret, mesh=self.mesh,
                reduce=self.reduce)
            self._fns[key] = fn
        return fn

    # -- elastic re-plan ----------------------------------------------------
    def _replan(self, k: int, failed: Set[str]):
        """Shrink the fleet after confirmed failures and continue the
        remaining units; completed units stay checkpointed."""
        for n in sorted(failed):
            self.monitor.evict(n)
        self._pending_replace -= failed
        alive = self.monitor.nodes
        if not alive:
            raise SweepUnitError(
                f"unit {k}: every worker is confirmed failed; "
                f"cannot re-plan the campaign")
        event = {"unit": k, "dropped": sorted(failed),
                 "n_alive": len(alive)}
        if self.mesh is not None:
            plan = plan_downscale(len(alive), model=1,
                                  data=self._initial_ndev, pods=1)
            # clamp the new width to one that divides the (fixed) padded
            # unit size, so the checkpoint layout survives the downscale
            nd = 1
            while (nd * 2 <= plan.n_devices
                   and self._padded_unit % (nd * 2) == 0):
                nd *= 2
            devices = [self._node_device[n] for n in alive
                       if n in self._node_device][:nd]
            self.mesh = jax.sharding.Mesh(np.array(devices), ("data",))
            self._mesh_epoch += 1
            self._fns.clear()     # recompile once per re-plan, not per unit
            event["elastic_plan"] = {
                "mesh_shape": list(plan.mesh_shape),
                "n_devices": nd,
                "grad_accum_factor": plan.grad_accum_factor}
        self.report.replans.append(event)

    # -- execution ----------------------------------------------------------
    def _execute(self, k: int):
        """One unit through retry + degradation.  Returns
        (stage, attempts_on_stage, seconds, SweepResult)."""
        idx, hw, gi, lane = self._unit_args(k)
        chain = self._chain if self.retry.degrade else self._chain[:1]
        errors: List[str] = []
        for stage in chain:
            for attempt in range(1, self.retry.max_attempts + 1):
                self.report.attempts_total += 1
                try:
                    if self.injector is not None:
                        self.injector.on_attempt(k, attempt, stage.name)
                    t0 = self.clock()
                    fn = self._fn_for(stage)
                    res = fn(idx, hw, gi) if lane is None \
                        else fn(idx, hw, gi, lane)
                    res = jax.block_until_ready(res)
                    secs = self.clock() - t0
                    if self.injector is not None:
                        secs += self.injector.extra_seconds(k)
                    if (self.retry.unit_timeout_s is not None
                            and secs > self.retry.unit_timeout_s):
                        raise UnitTimeout(
                            f"unit {k}: {secs:.3f}s exceeded the "
                            f"{self.retry.unit_timeout_s:.3f}s deadline")
                    return stage, attempt, secs, res
                except BackendFault as e:
                    errors.append(f"{stage.name}: {e}")
                    break                 # persistent: degrade immediately
                except Exception as e:  # noqa: BLE001 - any backend error
                    errors.append(f"{stage.name} attempt {attempt}: {e}")
                    if attempt < self.retry.max_attempts:
                        self.sleep(self.retry.backoff_s
                                   * self.retry.backoff_mult
                                   ** (attempt - 1))
            # retries exhausted on this stage -> next rung of the chain
        raise SweepUnitError(
            f"unit {k} [{self._unit_range(k)[0]}:{self._unit_range(k)[1]}) "
            f"failed on every backend of the chain "
            f"{[s.name for s in chain]}: " + "; ".join(errors))

    def run_unit(self, k: int) -> Tuple[UnitRecord, Dict[str, np.ndarray]]:
        """Execute (and commit) one pending unit."""
        lo, hi = self._unit_range(k)
        # every live worker participates in the unit (SPMD) and beats;
        # injected-dead nodes go silent from their configured unit on
        for n in self.monitor.nodes:
            if self.injector is None or not self.injector.node_dead(n, k):
                self.monitor.beat(n)
        failed = set(self.monitor.confirmed_failed()) | self._pending_replace
        if failed:
            self._replan(k, failed)
        node = self.monitor.nodes[k % len(self.monitor.nodes)]

        stage, attempts, secs, res = self._execute(k)
        if self.reduce is not None:
            # compacted (G, K) candidate set -- kilobytes, not the lane
            # slice; pad lanes were masked on device, nothing to trim
            res_np = {f: np.asarray(getattr(res, f))
                      for f in _pareto.REDUCED_FIELDS}
        else:
            res_np = {f: np.asarray(getattr(res, f))[:hi - lo]
                      for f in RESULT_FIELDS}
        if stage.name != self._chain[0].name:
            self.report.degraded[k] = stage.name
        rec = UnitRecord(unit=k, lo=lo, hi=hi, backend=stage.name,
                         attempts=attempts, resumed=False, seconds=secs,
                         node=node)
        self.report.units_run += 1
        self.report.records.append(rec)

        actions = self.monitor.observe_unit(node, secs)
        for n, act in actions.items():
            self.report.straggler_actions.append(
                {"unit": k, "node": n, "action": act})
            if (self.report.suggested_unit_size is None
                    and self.unit_size > 1):
                self.report.suggested_unit_size = max(self.unit_size // 2, 1)
            if act == "replace":
                self._pending_replace.add(n)

        self._results[k] = res_np
        if self.mgr is not None:
            if self.injector is not None:
                self.injector.on_commit(k)     # kill point: pre-durability
            self.mgr.save(res_np, k, extra={
                "fingerprint": self.fingerprint, "lo": lo, "hi": hi,
                "backend": stage.name, "attempts": attempts,
            }, block=not self.ckpt_async)
        if self.on_unit is not None:
            self.on_unit(rec, res_np)
        return rec, res_np

    def mark_skipped(self, k: int):
        """Give up on a unit (deadline-expired request): its lanes stitch
        as zeros and the report counts it."""
        if k not in self._results and k not in self._skipped:
            self._skipped.add(k)
            self.report.units_skipped += 1

    # -- stitching ----------------------------------------------------------
    def stitch(self, *, require_complete: bool = True
               ) -> Union[SweepResult, _pareto.ReducedResult]:
        """Assemble the full-grid ``SweepResult`` from unit results
        (checkpointed + freshly run).  Skipped units stitch as zeros.

        Under ``reduce`` the unit candidate sets merge associatively
        into the campaign ``ReducedResult`` instead (skipped units
        simply contribute no candidates)."""
        missing = self.pending_units()
        if missing and require_complete:
            raise SweepUnitError(
                f"cannot stitch: units {missing} incomplete")
        if self.reduce is not None:
            parts = [_pareto.ReducedResult(
                **{f: res[f] for f in _pareto.REDUCED_FIELDS})
                for _, res in sorted(self._results.items())]
            if not parts:
                return _pareto.ReducedResult(
                    **_pareto.reduced_zeros(self.G, self.reduce))
            return _pareto.merge_reduced(self.reduce, parts)
        out = {f: np.zeros(self.B, _RESULT_DTYPES[f])
               for f in RESULT_FIELDS}
        for k, res in self._results.items():
            lo, hi = self._unit_range(k)
            for f in RESULT_FIELDS:
                out[f][lo:hi] = res[f]
        return SweepResult(**{f: jnp.asarray(out[f])
                              for f in RESULT_FIELDS})

    def stitch_folded(self, *, require_complete: bool = True
                      ) -> _pareto.ReducedResult:
        """Stitch a reduced mapping campaign and fold the per-candidate
        rows to each kernel's best-mapping front
        (``analysis.pareto.fold_segments`` over the MappingSet's
        ``kernel_of`` segment map).  Candidate flat indices keep their
        candidate-lane coordinates, so the winning mapping id is
        ``mappings.mapping_of[idx // (H*D)]``.  Requires ``mappings=``
        and ``reduce=``; the fold is a host-side O(G*K) pass, so
        crash-safety is untouched -- checkpointed units stay
        per-candidate and a resumed campaign folds bit-identically."""
        if self.mappings is None or self.reduce is None:
            raise ValueError(
                "stitch_folded needs a mapping campaign (mappings=) "
                "with an on-device reduction (reduce=)")
        part = self.stitch(require_complete=require_complete)
        return _pareto.fold_segments(self.reduce, part,
                                     self.mappings.kernel_of,
                                     self.mappings.n_kernels)

    def run(self) -> Tuple[Union[SweepResult, _pareto.ReducedResult],
                           RunnerReport]:
        """Execute every pending unit (resuming from checkpoints), wait
        for the last async save, and stitch."""
        t0 = self.clock()
        for k in self.pending_units():
            self.run_unit(k)
        if self.mgr is not None:
            self.mgr.wait()
        self.report.wall_s = self.clock() - t0
        return self.stitch(require_complete=False), self.report


# -- CLI (subprocess target of kill-and-resume tests) -----------------------

_SMALL_KERNELS = {
    "bitcnt": lambda: None,       # populated lazily below (jax import cost)
}


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="resumable checkpointed DSE sweep (service runner)")
    ap.add_argument("--kernels", default="bitcnt,crc32",
                    help="comma list: bitcnt,crc32,susan,sha (small sizes)")
    ap.add_argument("--topos", default="baseline,c_interleaved")
    ap.add_argument("--backend", default="xla")
    ap.add_argument("--unit-size", type=int, default=4)
    ap.add_argument("--max-steps", type=int, default=256)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--reduce", default=None,
                    help="on-device reduction spec, e.g. 'topk:energy_pj:4'"
                         " or 'pareto:latency_cc,energy_pj:8' (see "
                         "analysis.pareto.spec_from_str)")
    ap.add_argument("--out", default=None, help=".npz of the SweepResult")
    ap.add_argument("--report-out", default=None, help="report JSON path")
    args = ap.parse_args(argv)

    from ..apps import mibench
    from ..core.characterization import default_profile
    from ..core.hwconfig import TOPOLOGIES
    from ..runtime.faults import FaultPlan

    small = {"bitcnt": lambda: mibench.bitcnt(n_words=16),
             "crc32": lambda: mibench.crc32(n_words=3),
             "susan": lambda: mibench.susan_thresh(n_pixels=16),
             "sha": lambda: mibench.sha_mix(rounds=8)}
    ks = [small[n.strip()]() for n in args.kernels.split(",")]
    hws = [TOPOLOGIES[t.strip()]() for t in args.topos.split(",")]
    mems = np.stack([k.mem_init for k in ks])

    fault_plan = FaultPlan.from_env()
    injector = FaultInjector(fault_plan) if fault_plan is not None else None
    red = _pareto.spec_from_str(args.reduce) if args.reduce else None
    runner = ResumableSweepRunner(
        programs=[k.program for k in ks], profile=default_profile(),
        hw_configs=hws, mem_images=mems, ckpt_dir=args.ckpt_dir,
        unit_size=args.unit_size, max_steps=args.max_steps,
        backend=args.backend, injector=injector, reduce=red)
    res, report = runner.run()
    if args.out:
        fields = _pareto.REDUCED_FIELDS if red is not None \
            else RESULT_FIELDS
        np.savez(args.out, **{f: np.asarray(getattr(res, f))
                              for f in fields})
    if args.report_out:
        Path(args.report_out).write_text(json.dumps(report.to_dict()))
    print(f"[sweep-runner] B={runner.B} lanes in {report.units_total} "
          f"units: run {report.units_run}, resumed {report.units_resumed}, "
          f"degraded {len(report.degraded)}, replans "
          f"{len(report.replans)}, wall {report.wall_s:.2f}s")
    return res, report


if __name__ == "__main__":
    main()

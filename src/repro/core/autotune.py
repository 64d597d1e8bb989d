"""Per-shape autotune cache for the sweep engine.

``blk_b`` (Pallas batch tile), ``chunk_steps`` (early-exit chunk) and
``max_buckets`` (length-bucket count of a packed multi-kernel sweep) all
depend on the *shape class* of a sweep -- ``(G, t_max, H, D, backend,
n_devices)`` -- not on the kernel contents.  This module gives the DSE
stack one answer to "what config should this shape run with":

  * ``AutotuneCache.resolve`` fills any ``AUTO`` knob from a persisted
    JSON cache of previously timed winners, falling back to the static
    defaults (32 / 64 / 4) on a miss -- so an untuned system behaves
    exactly as before;
  * ``tune_sweep`` times a small candidate grid on the *actual* sweep
    (first encounter of a shape class, or an explicit pre-warm pass) and
    persists the winner, so the heterogeneous request mix a real service
    sees is tuned automatically;
  * the cache file is schema-validated (``autotune_schema.json``, the
    same discipline as ``benchmarks/bench_schema.json``): a corrupt file,
    a stale version, or a malformed entry is *dropped*, never fatal --
    the cache is an accelerator, not a dependency.

Consulted by ``dse.sweep`` (every knob defaults to ``AUTO``), by
``service.runner.ResumableSweepRunner`` (blk_b / chunk_steps) and by
``service.server.SweepService`` (bucket count of request packing).
Opt into *automatic* first-encounter tuning with ``REPRO_AUTOTUNE=1``
(or ``dse.sweep(..., autotune=True)``); cache location override:
``REPRO_AUTOTUNE_CACHE=/path/to/cache.json``.
"""
from __future__ import annotations

import contextlib
import dataclasses
import json
import os
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Union

try:
    import fcntl
except ImportError:          # non-POSIX: saves fall back to atomic
    fcntl = None             # last-writer-wins (the pre-lock behavior)

# The sentinel for "let the autotuner decide".  A distinct object (not
# None): ``chunk_steps=None`` already means "disable chunking" in the
# sweep API, so AUTO must be distinguishable from an explicit None.
AUTO = "auto"

DEFAULT_BLK_B = 32       # XLA path: lane block of the eager dispatch
PALLAS_BLK_B = 128       # Pallas lane tile: one TPU vreg, the smallest legal
DEFAULT_CHUNK_STEPS = 64
DEFAULT_MAX_BUCKETS = 4
CACHE_VERSION = 1
_ENV_CACHE = "REPRO_AUTOTUNE_CACHE"
_ENV_ENABLE = "REPRO_AUTOTUNE"


def is_auto(*values) -> bool:
    """True if ANY of the values is the AUTO sentinel."""
    return any(isinstance(v, str) and v == AUTO for v in values)


def default_blk_b(backend: str) -> int:
    """The untuned lane tile of a backend."""
    return PALLAS_BLK_B if backend == "pallas" else DEFAULT_BLK_B


def autotune_enabled(flag: Optional[bool] = None) -> bool:
    """Explicit flag wins; otherwise the REPRO_AUTOTUNE env opt-in."""
    if flag is not None:
        return bool(flag)
    return os.environ.get(_ENV_ENABLE, "") not in ("", "0", "false", "no")


@dataclasses.dataclass(frozen=True)
class ShapeClass:
    """The tuning key: what a sweep *looks like* to the engine.  H and D
    are the hardware/data grid extents for ``dse.sweep``; the service's
    merged plans use ``H = lanes per program, D = 1`` as the lane-shape
    proxy (same key space, same recurrence behavior)."""
    G: int
    t_max: int
    H: int
    D: int
    backend: str
    n_devices: int = 1

    @property
    def key(self) -> str:
        return (f"g{self.G}-t{self.t_max}-h{self.H}-d{self.D}-"
                f"{self.backend}-dev{self.n_devices}")


@dataclasses.dataclass(frozen=True)
class TunedConfig:
    """A resolved knob set.  ``source`` records where it came from:
    ``"default"`` (static fallbacks), ``"cache"`` (persisted winner),
    ``"tuned"`` (just timed), ``"explicit"`` (caller pinned every
    knob).  ``backend`` is set only on backend-*choice* entries (shape
    classes keyed with ``backend=AUTO``): the engine that won the
    xla-vs-pallas timing for that shape."""
    blk_b: int
    chunk_steps: Optional[int]
    max_buckets: int
    source: str = "default"
    points_per_s: Optional[float] = None
    backend: Optional[str] = None


def _valid_entry(e) -> bool:
    """One cache entry against autotune_schema.json's constraints (the
    subset that matters for safety); invalid entries are skipped."""
    if not isinstance(e, dict) or "chunk_steps" not in e:
        return False
    bb = e.get("blk_b")
    if not (isinstance(bb, int) and not isinstance(bb, bool) and bb >= 1):
        return False
    cs = e["chunk_steps"]
    if cs is not None and not (isinstance(cs, int)
                               and not isinstance(cs, bool) and cs >= 1):
        return False
    mb = e.get("max_buckets")
    if not (isinstance(mb, int) and not isinstance(mb, bool) and mb >= 1):
        return False
    pps = e.get("points_per_s")
    if pps is not None and not isinstance(pps, (int, float)):
        return False
    be = e.get("backend")
    if be is not None and be not in ("xla", "pallas"):
        return False
    return True


def _default_path() -> Path:
    env = os.environ.get(_ENV_CACHE, "")
    if env:
        return Path(env)
    return Path.home() / ".cache" / "repro" / "autotune.json"


class AutotuneCache:
    """Schema-validated JSON store of per-shape winners.

    Load is maximally tolerant: unreadable file / invalid JSON / wrong
    version / malformed entries all degrade to "no cached winner" --
    ``resolve`` then falls back to the static defaults.  Saves are
    atomic (tmp + rename), so a crash mid-save never corrupts winners
    already persisted, AND merge under an ``fcntl`` file lock: a save
    re-reads the on-disk entries and unions them with this process's
    (ours win per key), so concurrent service workers warm each other's
    shape classes instead of last-writer-wins dropping them.  If the
    lock cannot be taken within ``lock_timeout_s`` (or the platform has
    no ``fcntl``), the save degrades to the plain atomic write -- the
    cache is an accelerator, never a point of contention."""

    def __init__(self, path: Optional[Union[str, Path]] = None, *,
                 lock_timeout_s: float = 1.0):
        self.path = Path(path) if path is not None else _default_path()
        self.lock_timeout_s = lock_timeout_s
        self.entries: Dict[str, dict] = {}
        self._load()

    def _read_entries(self) -> Dict[str, dict]:
        """Current on-disk entries (schema-filtered); {} on any damage."""
        try:
            raw = json.loads(self.path.read_text())
        except (OSError, ValueError):
            return {}
        if not isinstance(raw, dict) \
                or raw.get("version") != CACHE_VERSION \
                or not isinstance(raw.get("entries"), dict):
            return {}                        # stale/foreign cache: ignore
        return {k: v for k, v in raw["entries"].items()
                if isinstance(k, str) and _valid_entry(v)}

    def _load(self) -> None:
        self.entries = self._read_entries() or self.entries

    @contextlib.contextmanager
    def _locked(self):
        """Yield True holding an exclusive lock on ``<cache>.lock``,
        False when the lock is unavailable (timeout / no fcntl)."""
        if fcntl is None or self.lock_timeout_s <= 0:
            yield False
            return
        lock_path = self.path.with_name(self.path.name + ".lock")
        try:
            fd = os.open(str(lock_path), os.O_CREAT | os.O_RDWR, 0o644)
        except OSError:
            yield False
            return
        try:
            deadline = time.monotonic() + self.lock_timeout_s
            while True:
                try:
                    fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
                    break
                except OSError:
                    if time.monotonic() >= deadline:
                        yield False
                        return
                    time.sleep(0.01)
            try:
                yield True
            finally:
                fcntl.flock(fd, fcntl.LOCK_UN)
        finally:
            os.close(fd)

    def save(self) -> None:
        self.path.parent.mkdir(parents=True, exist_ok=True)
        with self._locked() as held:
            if held:
                # read-merge-write: union the entries some other worker
                # persisted since our load; our own keys win conflicts
                merged = self._read_entries()
                merged.update(self.entries)
                self.entries = merged
            payload = {"version": CACHE_VERSION, "entries": self.entries}
            fd, tmp = tempfile.mkstemp(dir=str(self.path.parent),
                                       prefix=self.path.name, suffix=".tmp")
            try:
                with os.fdopen(fd, "w") as f:
                    json.dump(payload, f, indent=2, sort_keys=True)
                    f.write("\n")
                os.replace(tmp, self.path)
            except OSError:
                try:
                    os.unlink(tmp)
                except OSError:
                    pass

    def lookup(self, shape: ShapeClass) -> Optional[TunedConfig]:
        e = self.entries.get(shape.key)
        if e is None:
            return None
        return TunedConfig(blk_b=e["blk_b"], chunk_steps=e["chunk_steps"],
                           max_buckets=e["max_buckets"], source="cache",
                           points_per_s=e.get("points_per_s"),
                           backend=e.get("backend"))

    def store(self, shape: ShapeClass, cfg: TunedConfig) -> None:
        self.entries[shape.key] = {
            "blk_b": int(cfg.blk_b),
            "chunk_steps": (None if cfg.chunk_steps is None
                            else int(cfg.chunk_steps)),
            "max_buckets": int(cfg.max_buckets),
            "points_per_s": cfg.points_per_s,
            "backend": cfg.backend,
            "shape": dataclasses.asdict(shape),
        }
        self.save()

    def resolve(self, shape: ShapeClass, *,
                blk_b: Union[int, str] = AUTO,
                chunk_steps: Union[int, None, str] = AUTO,
                max_buckets: Union[int, str] = AUTO) -> TunedConfig:
        """Fill AUTO knobs from the cache, else the static defaults;
        explicit (non-AUTO) knobs always win."""
        cached = self.lookup(shape) if is_auto(blk_b, chunk_steps,
                                               max_buckets) else None
        if not is_auto(blk_b, chunk_steps, max_buckets):
            source = "explicit"
        elif cached is not None:
            source = "cache"
        else:
            source = "default"

        def pick(explicit, cached_v, default):
            if not is_auto(explicit):
                return explicit
            return cached_v if cached is not None else default

        return TunedConfig(
            blk_b=int(pick(blk_b, cached.blk_b if cached else None,
                           default_blk_b(shape.backend))),
            chunk_steps=pick(chunk_steps,
                             cached.chunk_steps if cached else None,
                             DEFAULT_CHUNK_STEPS),
            max_buckets=int(pick(max_buckets,
                                 cached.max_buckets if cached else None,
                                 DEFAULT_MAX_BUCKETS)),
            source=source,
            points_per_s=cached.points_per_s if cached else None)


_caches: Dict[str, AutotuneCache] = {}


def default_cache() -> AutotuneCache:
    """Process-wide cache for the current REPRO_AUTOTUNE_CACHE target
    (re-resolved per call so tests can repoint the env)."""
    key = str(_default_path())
    c = _caches.get(key)
    if c is None:
        c = _caches[key] = AutotuneCache()
    return c


def resolve_backend(shape: ShapeClass, *,
                    cache: Optional[AutotuneCache] = None,
                    default: str = "xla") -> str:
    """Resolve a ``backend=AUTO`` request for a shape class.

    ``shape`` must be keyed with ``backend=AUTO`` (backend-choice
    entries live in the same cache, under the AUTO-keyed shape).
    Precedence is decided at the call sites: an explicit backend never
    reaches here; a cached xla-vs-pallas winner is used when present;
    otherwise ``default``.  Timing new shapes is ``tune_sweep``'s job --
    this helper never compiles anything, so the resumable runner and
    the service can resolve AUTO without perturbing campaign wall time.
    """
    cfg = (cache or default_cache()).lookup(shape)
    if cfg is not None and cfg.backend in ("xla", "pallas"):
        return cfg.backend
    return default


def default_candidates(shape: ShapeClass, max_steps: int) -> List[dict]:
    """The small first-encounter candidate grid: bucket counts that make
    sense for G, early-exit chunk sizes around the default, and (Pallas
    only) two batch tiles."""
    buckets = sorted({b for b in (1, 2, 4, min(shape.G, 8))
                      if 1 <= b <= shape.G})
    chunks = sorted({c for c in (32, 64, 128) if c <= max(max_steps, 32)})
    blks = ((PALLAS_BLK_B, 2 * PALLAS_BLK_B) if shape.backend == "pallas"
            else (DEFAULT_BLK_B,))
    return [dict(max_buckets=b, chunk_steps=c, blk_b=k)
            for b in buckets for c in chunks for k in blks]


def tune_sweep(programs, profile, hw_configs, mem_images, *,
               backend: str = "xla", max_steps: int = 2048,
               mem_size: int = 4096, mesh=None, interpret=None,
               cache: Optional[AutotuneCache] = None,
               candidates: Optional[Sequence[dict]] = None,
               repeats: int = 2) -> TunedConfig:
    """Time the candidate grid on the actual sweep and persist the winner.

    Each candidate is compiled+warmed once, then timed ``repeats`` times
    (min taken -- noise-robust for short sweeps).  The winner lands in
    the cache keyed by the sweep's shape class, so every later
    ``dse.sweep``/service call of that shape picks it up for free.

    ``backend=AUTO`` makes the *backend itself* a tuned knob: both
    engines (xla scan vs pallas) are timed over their candidate grids;
    each engine's winner is persisted under its concrete-backend shape
    key, and the overall winner lands under the AUTO-keyed shape with
    ``TunedConfig.backend`` set -- later ``backend=AUTO`` calls of that
    shape (``dse.sweep``, the service, the resumable runner) resolve
    through ``resolve_backend`` without re-timing.

    Import of dse is deferred (dse imports this module)."""
    import jax

    from . import dse
    from .program import as_program_batch

    batch = as_program_batch(programs)
    G = batch.n_programs
    H, D = len(hw_configs), int(mem_images.shape[0])
    n_devices = int(mesh.devices.size) if mesh is not None else 1
    backends = ("xla", "pallas") if is_auto(backend) else (backend,)
    B = G * H * D
    store = cache or default_cache()
    best = None                               # (pps, cand, concrete backend)
    for be in backends:
        shape_b = ShapeClass(G=G, t_max=batch.t_max, H=H, D=D, backend=be,
                             n_devices=n_devices)
        cands = list(candidates) if candidates is not None \
            else default_candidates(shape_b, max_steps)
        best_b = None
        for cand in cands:
            def run():
                jax.block_until_ready(dse.sweep(
                    program=batch, profile=profile, hw_configs=hw_configs,
                    mem_images=mem_images, mesh=mesh, max_steps=max_steps,
                    mem_size=mem_size, backend=be, interpret=interpret,
                    chunk_steps=cand["chunk_steps"], blk_b=cand["blk_b"],
                    max_buckets=cand["max_buckets"], autotune=False))
            run()                             # compile + warm
            ts = []
            for _ in range(max(1, repeats)):
                t0 = time.perf_counter()
                run()
                ts.append(time.perf_counter() - t0)
            pps = B / max(min(ts), 1e-9)
            if best_b is None or pps > best_b[0]:
                best_b = (pps, cand)
        pps, cand = best_b
        store.store(shape_b, TunedConfig(
            blk_b=cand["blk_b"], chunk_steps=cand["chunk_steps"],
            max_buckets=cand["max_buckets"], source="tuned",
            points_per_s=pps))
        if best is None or pps > best[0]:
            best = (pps, cand, be)
    pps, cand, be = best
    cfg = TunedConfig(blk_b=cand["blk_b"], chunk_steps=cand["chunk_steps"],
                      max_buckets=cand["max_buckets"], source="tuned",
                      points_per_s=pps,
                      backend=be if is_auto(backend) else None)
    if is_auto(backend):
        store.store(ShapeClass(G=G, t_max=batch.t_max, H=H, D=D,
                               backend=AUTO, n_devices=n_devices), cfg)
    return cfg

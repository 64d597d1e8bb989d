"""Logical-axis sharding with divisibility-aware fallback.

Every parameter and activation in the model zoo is annotated with *logical*
dimension names ("vocab", "embed", "mlp", "heads", ...).  A ``ShardingRules``
table maps each logical name to a *preference list* of mesh axes; the first
axis that (a) divides the dimension and (b) is not already consumed by
another dimension of the same tensor wins; otherwise the dimension is
replicated.  This makes one rule table serve all 10 assigned architectures
even where head counts (15, 28, 12, 4) or expert counts (8, 32) do not
divide the 16-way model axis -- the fallback chain picks the next workable
axis instead of failing to lower.

Production mesh axes: ("pod", "data", "model") multi-pod / ("data",
"model") single-pod.  DP/FSDP ride ("pod","data"); TP/EP/SP ride "model".
"""
from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

Axes = Tuple[str, ...]

# Preference chains per logical dimension name.  Order matters: the first
# mesh axis whose size divides the dim (and is still free) is chosen.
DEFAULT_RULES: Dict[str, Axes] = {
    # --- parameters -------------------------------------------------------
    "vocab": ("model",),             # TP over the vocabulary (logit matmul)
    "embed": ("data", "pod"),        # FSDP: shard d_model rows over DP axes
    "embed_tp": ("model",),          # d_model when it is the TP dim
    "mlp": ("model",),               # FFN hidden (Megatron column/row)
    "heads": ("model",),             # query heads
    "kv_heads": ("model",),          # kv heads (replicated when < axis)
    "head_dim": (),                  # only sharded under attn_tp=head_dim
    "head_dim_tp": ("model",),
    "qkv": ("model",),               # flattened q/k/v output dim
    "experts": ("model", "data"),    # EP; falls back to DP-sharded experts
    "expert_mlp": ("model",),        # per-expert hidden when EP impossible
    "conv": (),                      # small conv kernels: replicated
    "ssm_inner": ("model",),         # mamba2 inner channels
    "ssm_heads": ("model",),
    "ssm_state": (),
    # --- activations ------------------------------------------------------
    "batch": ("pod", "data"),        # NOTE: tried in order, combined below
    "seq": (),                       # SP off by default (opt-in per config)
    "seq_sp": ("model",),            # context/sequence parallelism
    "act_embed": (),                 # activations replicated over model by
    "act_mlp": ("model",),           #   default; mlp/heads TP-sharded
    "act_heads": ("model",),
    "act_kv": (),
    "cache_batch": ("data",),
    # decode caches shard their context dim over the TP axis: attention
    # over a seq-sharded cache is flash-decoding (GSPMD inserts the
    # partial-softmax combine); this is also what bounds long_500k memory.
    "cache_seq": ("model",),
    "cache_heads": ("model",),
    # --- optimizer --------------------------------------------------------
    "none": (),
}

# Logical names whose preference list should be *combined* (meshes axes
# tupled together) rather than tried in order, e.g. batch over pod AND data.
_COMBINE = {"batch": ("pod", "data"), "embed": ("data", "pod")}


class ShardingRules:
    def __init__(self, table: Optional[Dict[str, Axes]] = None,
                 combine: Optional[Dict[str, Axes]] = None):
        self.table = dict(DEFAULT_RULES)
        if table:
            self.table.update(table)
        self.combine = dict(_COMBINE)
        if combine is not None:
            self.combine = dict(combine)

    def with_overrides(self, **kw: Axes) -> "ShardingRules":
        r = ShardingRules(self.table, self.combine)
        r.table.update(kw)
        return r


def _axis_size(mesh: Mesh, name: str) -> int:
    try:
        return mesh.shape[name]
    except (KeyError, TypeError):
        return 0


def logical_to_spec(logical: Sequence[Optional[str]], shape: Sequence[int],
                    mesh: Mesh, rules: Optional[ShardingRules] = None
                    ) -> P:
    """Resolve logical dim names -> PartitionSpec for `mesh`.

    Combined names (e.g. "batch") may claim several axes at once if the
    product divides the dim; otherwise they degrade to the longest
    divisible prefix.  Every mesh axis is used at most once per tensor.
    """
    rules = rules or current_rules()
    used: set = set()
    out = []
    for dim, name in zip(shape, logical):
        if name is None or name not in rules.table and name not in \
                rules.combine:
            out.append(None)
            continue
        # combined axes: use the longest prefix of available axes whose
        # product divides the dimension
        if name in rules.combine:
            cand = [a for a in rules.combine[name]
                    if _axis_size(mesh, a) > 0 and a not in used]
            chosen: list = []
            prod = 1
            for a in cand:
                if dim % (prod * _axis_size(mesh, a)) == 0:
                    chosen.append(a)
                    prod *= _axis_size(mesh, a)
            if chosen:
                used.update(chosen)
                out.append(tuple(chosen) if len(chosen) > 1 else chosen[0])
            else:
                out.append(None)
            continue
        for a in rules.table.get(name, ()):
            sz = _axis_size(mesh, a)
            if sz > 0 and a not in used and dim % sz == 0:
                used.add(a)
                out.append(a)
                break
        else:
            out.append(None)
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def spec_tree(axes_tree, shapes_tree, mesh: Mesh,
              rules: Optional[ShardingRules] = None):
    """Map a tree of logical-axes tuples + matching shapes -> NamedShardings."""
    def one(axes, shaped):
        shape = shaped.shape if hasattr(shaped, "shape") else shaped
        return NamedSharding(mesh, logical_to_spec(axes, shape, mesh, rules))
    return jax.tree.map(one, axes_tree, shapes_tree,
                        is_leaf=lambda x: isinstance(x, tuple) and all(
                            isinstance(e, (str, type(None))) for e in x))


# ---------------------------------------------------------------------------
# Activation constraints: thread-local (mesh, rules) context so model code
# can annotate without plumbing the mesh through every call.
# ---------------------------------------------------------------------------

_CTX = threading.local()


@contextlib.contextmanager
def use_mesh_rules(mesh: Optional[Mesh], rules: Optional[ShardingRules]):
    prev = getattr(_CTX, "state", None)
    _CTX.state = (mesh, rules or ShardingRules())
    try:
        yield
    finally:
        _CTX.state = prev


def set_rules(mesh: Optional[Mesh], rules: Optional[ShardingRules] = None):
    _CTX.state = (mesh, rules or ShardingRules())


def current_rules() -> ShardingRules:
    st = getattr(_CTX, "state", None)
    return st[1] if st else ShardingRules()


def current_mesh() -> Optional[Mesh]:
    st = getattr(_CTX, "state", None)
    return st[0] if st else None


def constrain(x, *logical: Optional[str]):
    """with_sharding_constraint by logical names; identity when no mesh is
    active (smoke tests on 1 device)."""
    mesh = current_mesh()
    if mesh is None or len(mesh.devices.reshape(-1)) <= 1:
        return x
    spec = logical_to_spec(logical, x.shape, mesh, current_rules())
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


# ---------------------------------------------------------------------------
# Flat-batch sweep sharding: the DSE (program x hw x data) grid is one
# long batch axis spread over EVERY axis of whatever mesh the caller
# brings ((data,), (pod, data, model), ...).  Used by the one shard_map
# sweep path of both backends (core/dse.py ``_shard_call``): the
# per-lane index vectors (img_idx, prog_idx) and the stacked HwConfig
# leaves all shard with flat_batch_spec, while the gathered-by-index
# payloads (memory images, packed program tables) stay replicated.
# ---------------------------------------------------------------------------

def flat_batch_spec(mesh: Mesh) -> P:
    """PartitionSpec sharding a leading batch axis over all mesh axes."""
    return P(tuple(mesh.axis_names))


def padded_len(n: int, n_devices: int) -> int:
    """Smallest multiple of ``n_devices`` >= n (flat-grid pad target)."""
    return -(-n // n_devices) * n_devices


def replicated_sharding(mesh: Mesh) -> NamedSharding:
    """Fully-replicated NamedSharding on `mesh`."""
    return NamedSharding(mesh, P())


def pad_batch(x: jnp.ndarray, target: int, fill=None) -> jnp.ndarray:
    """Pad a leading batch axis up to `target` rows.

    By default the pad repeats row 0: sweep lanes are independent, so
    duplicated rows are harmless redundant work; callers slice outputs
    back to the true length.  Used to make an arbitrary design-point
    count divisible by the device count before shard_map.

    With ``fill`` the pad rows are that constant instead — the on-device
    reduction path pads its ``lane_idx`` operand with ``fill=-1`` so the
    duplicate lanes are *masked* (a repeated lane must not appear twice
    in a top-k candidate set)."""
    pad = target - x.shape[0]
    if pad <= 0:
        return x
    if fill is not None:
        return jnp.concatenate(
            [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)])
    return jnp.concatenate(
        [x, jnp.broadcast_to(x[:1], (pad,) + x.shape[1:])])

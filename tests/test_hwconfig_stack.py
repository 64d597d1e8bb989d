"""Batched hardware configurations are built on the host and moved to the
device once per field; what reaches the sweep core is bit-identical to
stacking one device array per field per configuration."""
import itertools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.apps import mibench
from repro.core import dse
from repro.core.hwconfig import TOPOLOGIES, HwConfig, stack_configs
from repro.service.server import SweepRequest, _merge_plans

FLOAT_FIELDS = ("smul_power_scale", "t_clk_ns")


def _per_leaf_stack(configs):
    """The construction the sweep core was built against: one
    ``jnp.asarray`` per field per configuration, stacked per field."""
    return {f: jnp.stack([jnp.asarray(getattr(c, f), jnp.float32)
                          if f in FLOAT_FIELDS
                          else jnp.asarray(getattr(c, f), jnp.int32)
                          for c in configs]) for f in HwConfig.FIELDS}


def _tiled(fields, D, G):
    return {f: jnp.tile(jnp.repeat(x, D, axis=0), G)
            for f, x in fields.items()}


def _table2(n, kind="python"):
    """``n`` configurations over the five Table-2 topologies, varying
    ``n_banks``, ``smul_lat`` and ``t_mem``; each field given as a Python
    scalar, a numpy scalar or a 0-d jax array."""
    variants = itertools.cycle(itertools.product(
        TOPOLOGIES.values(), (2, 8, 32, 256), (1, 3), (1, 2, 4)))
    out = []
    for mk, n_banks, smul_lat, t_mem in itertools.islice(variants, n):
        hw = mk().replace(n_banks=n_banks, t_mem=t_mem,
                          smul_lat=smul_lat)
        if kind == "numpy":
            hw = hw.replace(**{f: (np.float64 if isinstance(v, float)
                                   else np.int64)(v)
                               for f, v in hw.as_dict().items()})
        elif kind == "jax":
            hw = hw.replace(**{f: jnp.asarray(v)
                               for f, v in hw.as_dict().items()})
        out.append(hw)
    return out


def _assert_same_leaves(got: HwConfig, want: dict):
    for f in HwConfig.FIELDS:
        g, w = getattr(got, f), want[f]
        assert isinstance(g, jax.Array), f
        assert g.dtype == w.dtype and g.shape == w.shape, f
        assert g.weak_type == w.weak_type, f
        assert np.asarray(g).tobytes() == np.asarray(w).tobytes(), f


@pytest.mark.parametrize("kind", ["python", "numpy", "jax"])
@pytest.mark.parametrize("n", [1, 48, 240])
def test_stack_configs_matches_per_leaf_stack(n, kind):
    hws = _table2(n, kind)
    _assert_same_leaves(stack_configs(hws), _per_leaf_stack(hws))


def test_stack_configs_inside_a_trace_stacks_the_tracers():
    hws = _table2(5)

    @jax.jit
    def traced(lat):
        return stack_configs([hws[0].replace(smul_lat=lat)] + hws[1:])

    want = _per_leaf_stack([hws[0].replace(smul_lat=7)] + hws[1:])
    _assert_same_leaves(traced(7), want)


@pytest.fixture(scope="module")
def kernels():
    return [mibench.bitcnt(n_words=16), mibench.crc32(n_words=3),
            mibench.susan_thresh()]


@pytest.mark.parametrize("G,H,D", [(2, 5, 3), (3, 48, 2)])
def test_plan_grid_hw_grid_matches_tiled_per_leaf_stack(kernels, G, H, D):
    hws = _table2(H)
    mems = np.stack([kernels[0].mem_init] * D)
    plan = dse.plan_grid([k.program for k in kernels[:G]], hws, mems)
    assert plan.n_lanes == G * H * D
    _assert_same_leaves(plan.hw_grid, _tiled(_per_leaf_stack(hws), D, G))
    assert plan.max_banks >= max(h.n_banks for h in hws)


def test_merge_plans_concatenates_each_requests_plan(kernels):
    hws = _table2(48)
    reqs = [SweepRequest(programs=[k.program for k in kernels[:2]],
                         hw_configs=hws[:5],
                         mem_images=np.stack([kernels[0].mem_init] * 3)),
            SweepRequest(programs=[kernels[2].program], hw_configs=hws,
                         mem_images=kernels[2].mem_init[None])]
    plan, members = _merge_plans(reqs)
    solo = [dse.plan_grid(list(r.programs), r.hw_configs, r.mem_images)
            for r in reqs]
    want = {f: jnp.concatenate([getattr(p.hw_grid, f) for p in solo])
            for f in HwConfig.FIELDS}
    _assert_same_leaves(plan.hw_grid, want)
    per_leaf = [_tiled(_per_leaf_stack(r.hw_configs), r.mem_images.shape[0],
                       len(r.programs)) for r in reqs]
    _assert_same_leaves(plan.hw_grid, {
        f: jnp.concatenate([p[f] for p in per_leaf])
        for f in HwConfig.FIELDS})
    assert [(lo, hi) for _, lo, hi in members] == [(0, 30), (30, 78)]
    assert plan.max_banks == max(p.max_banks for p in solo)

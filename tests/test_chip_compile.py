"""Compile the sweep engines for a TPU v5e that is described, not attached.

Interpret mode cannot show what Mosaic refuses (unaligned blocks,
unsupported gathers, VMEM overflow), so these tests compile the main
path at its real size -- the five MiBench kernels, 4,096-word memories,
B = 1,024 design points -- with the TPU compiler installed here:

  * the XLA operand core;
  * the Pallas engine with ``interpret=False`` (must hold a
    ``tpu_custom_call``);
  * the Pallas engine fused with the on-device reduction;
  * the one ``shard_map`` sweep path on a 2x2 mesh, for both backends.

Nothing runs, so nothing here is a result or a time.  The topology is
described inside a module fixture, never at import: only the worker that
runs this file loads the TPU library.
"""
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec, \
    SingleDeviceSharding

from repro.analysis.pareto import ParetoFront
from repro.apps import mibench
from repro.core import dse
from repro.core.hwconfig import HwConfig
from repro.core.memory import HARD_MAX_BANKS
from repro.core.program import as_program_batch
from repro.kernels.cgra_sweep import ops
from repro.kernels.cgra_sweep.kernel import N_FETCH

B = 1024
M = 4096
HBM_BYTES = 16 * 2 ** 30          # one v5e chip


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    was = jax.config.jax_enable_compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        desc = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any reason it cannot be built
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def campaign(profile):
    kernels = mibench.all_kernels()
    return dict(programs=[k.program for k in kernels], profile=profile,
                max_steps=max(k.max_steps for k in kernels))


def _hw(sharding, n=B):
    return HwConfig(*[jax.ShapeDtypeStruct(
        (n,), jnp.float32 if f in ("smul_power_scale", "t_clk_ns")
        else jnp.int32, sharding=sharding) for f in HwConfig.FIELDS])


def _lanes(sharding, n=B):
    return jax.ShapeDtypeStruct((n,), jnp.int32, sharding=sharding)


def _pallas_core(campaign, blk_b=128):
    batch = as_program_batch(campaign["programs"])
    p = campaign["profile"]
    return batch, ops._pallas_sweep_core(
        4, 4, M, batch.t_max, batch.n_programs, 64, campaign["max_steps"],
        blk_b, False, float(p.p_idle), float(p.e_sw_op),
        float(p.e_sw_mux), float(p.mulzero), float(p.t_clk_ns))


def _pallas_operands(campaign, batch, sharding):
    sds = lambda x: jax.ShapeDtypeStruct(np.shape(x), np.asarray(x).dtype,
                                         sharding=sharding)
    G, T = batch.n_programs, batch.t_max
    rows = -(-G * T // ops.LANE_WIDTH) * ops.LANE_WIDTH
    tab = jax.ShapeDtypeStruct((N_FETCH * 16, rows), jnp.bfloat16,
                               sharding=sharding)
    prof = tuple(sds(np.asarray(getattr(campaign["profile"], f),
                                np.float32))
                 for f in ("p_dec", "p_act", "e_src"))
    return (tab, sds(np.asarray(batch.n_instrs, np.int32)), prof,
            jax.ShapeDtypeStruct((B, M), jnp.int32, sharding=sharding),
            _hw(sharding), _lanes(sharding))


def test_xla_operand_core_compiles_for_v5e(one_chip, campaign):
    batch = as_program_batch(campaign["programs"])
    core = dse._xla_sweep_core(4, 4, M, campaign["max_steps"], 64,
                               HARD_MAX_BANKS, batch.t_max)
    R = batch.n_programs * batch.t_max
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,
                                                 sharding=one_chip)
    tbl = {k: sds(np.shape(v), v.dtype) for k, v in
           dse._profile_tables(campaign["profile"]).items()}
    compiled = core.lower(
        sds((R, 10, 16), jnp.int32), sds((batch.n_programs,), jnp.int32),
        tbl, sds((B, M), jnp.int32), _hw(one_chip),
        _lanes(one_chip)).compile()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


@pytest.mark.parametrize("blk_b", [128, 256])      # autotune's candidates
def test_pallas_engine_compiles_for_v5e(one_chip, campaign, blk_b):
    """Both lane tiles autotune may pick lower, with their VMEM."""
    batch, core = _pallas_core(campaign, blk_b)
    compiled = core.lower(*_pallas_operands(campaign, batch,
                                            one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


def test_reduced_pallas_core_compiles_for_v5e(one_chip, campaign):
    batch, core = _pallas_core(campaign)
    spec = ParetoFront(("latency_cc", "energy_pj"), 16)
    rcore = ops._reduced_core(core, spec, batch.n_programs)
    compiled = rcore.lower(*_pallas_operands(campaign, batch, one_chip),
                           _lanes(one_chip)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("backend", ["xla", "pallas"])
def test_shard_map_sweep_compiles_on_v5e_2x2(topo, campaign, backend):
    mesh = Mesh(np.array(topo.devices), ("data",))
    assert mesh.devices.size == 4
    fn = dse.make_sweep_fn(campaign["programs"], campaign["profile"],
                           mem_size=M, max_steps=campaign["max_steps"],
                           backend=backend, max_banks=HARD_MAX_BANKS,
                           interpret=False if backend == "pallas" else None,
                           validate=False)
    kernels = mibench.all_kernels()
    images = np.stack([k.mem_init for k in kernels])
    call = dse._shard_call(fn, images, mesh)
    flat = NamedSharding(mesh, PartitionSpec("data"))
    compiled = call.sharded.lower(
        jax.ShapeDtypeStruct(images.shape, jnp.int32,
                             sharding=NamedSharding(mesh, PartitionSpec())),
        _lanes(flat), _lanes(flat), _hw(flat)).compile()
    text = compiled.as_text()
    assert ("tpu_custom_call" in text) == (backend == "pallas")
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < HBM_BYTES


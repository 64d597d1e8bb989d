"""Hardware topology description of the CGRA + its memory subsystem.

This is the paper's Table 2 made explicit: the estimator can be pointed at
a different hardware configuration (bus type, bank interleaving, DMA
placement, accelerated multiplier) *without* any RTL rebuild -- the whole
point of the tool.

``HwConfig`` is a pytree of jnp-compatible scalars so that design-space
sweeps can ``vmap`` directly over stacked configurations (see dse.py).
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs

# Bus types.
BUS_ONE_TO_M = 0   # single memory port; all requests serialize globally
BUS_N_TO_M = 1     # banked; requests to different banks proceed in parallel


@jax.tree_util.register_pytree_node_class
class HwConfig:
    """CGRA + system topology (all leaves are scalars; vmap-able).

    Fields
    ------
    smul_lat:         multiplier latency in cc (3 baseline, 1 for mod (a))
    smul_power_scale: active-power scale of SMUL (3.0 for mod (a))
    bus:              BUS_ONE_TO_M | BUS_N_TO_M
    interleaved:      0 = blocked bank mapping (addr // bank_words),
                      1 = word-interleaved (addr % n_banks)
    n_banks:          number of SRAM banks (only meaningful for N-to-M)
    dma_per_pe:       0 = one DMA per column (baseline), 1 = one per PE
    t_mem:            uncontended memory access latency in cc
    t_clk_ns:         clock period (100 MHz -> 10 ns)
    """

    FIELDS = ("smul_lat", "smul_power_scale", "bus", "interleaved",
              "n_banks", "dma_per_pe", "t_mem", "t_clk_ns")

    def __init__(self, smul_lat=3, smul_power_scale=1.0, bus=BUS_ONE_TO_M,
                 interleaved=0, n_banks=4, dma_per_pe=0, t_mem=2,
                 t_clk_ns=10.0):
        self.smul_lat = smul_lat
        self.smul_power_scale = smul_power_scale
        self.bus = bus
        self.interleaved = interleaved
        self.n_banks = n_banks
        self.dma_per_pe = dma_per_pe
        self.t_mem = t_mem
        self.t_clk_ns = t_clk_ns

    # pytree protocol ------------------------------------------------------
    def tree_flatten(self):
        return tuple(getattr(self, f) for f in self.FIELDS), None

    @classmethod
    def tree_unflatten(cls, aux, leaves):
        cfg = cls.__new__(cls)
        for f, v in zip(cls.FIELDS, leaves):
            setattr(cfg, f, v)
        return cfg

    def replace(self, **kw) -> "HwConfig":
        d = {f: getattr(self, f) for f in self.FIELDS}
        d.update(kw)
        return HwConfig(**d)

    def as_dict(self) -> Dict[str, Any]:
        return {f: getattr(self, f) for f in self.FIELDS}

    def __repr__(self):
        return "HwConfig(" + ", ".join(
            f"{f}={getattr(self, f)}" for f in self.FIELDS) + ")"


# --------------------------------------------------------------------------
# The paper's topologies (Table 2).
# --------------------------------------------------------------------------

def baseline() -> HwConfig:
    """OpenEdgeCGRA as integrated in its host MCU: 1-to-M bus, one DMA per
    column, 3-cc multiplier."""
    return HwConfig()


def mod_a_fast_mul() -> HwConfig:
    """(a) accelerated SMUL: 1 cc instead of 3, at 3x the power."""
    return baseline().replace(smul_lat=1, smul_power_scale=3.0)


def mod_b_n_to_m() -> HwConfig:
    """(b) N-to-M bus: parallel accesses to distinct (blocked) banks."""
    return baseline().replace(bus=BUS_N_TO_M, interleaved=0)


def mod_c_interleaved() -> HwConfig:
    """(c) N-to-M bus with word-interleaved banks (consecutive addresses
    land in different banks)."""
    return baseline().replace(bus=BUS_N_TO_M, interleaved=1)


def mod_d_dma_per_pe() -> HwConfig:
    """(d) one DMA per PE (instead of per column) + N-to-M interleaved bus
    -- the bus type must be N-to-M for the extra ports to pay off (paper
    Section 3.2)."""
    return baseline().replace(bus=BUS_N_TO_M, interleaved=1, dma_per_pe=1)


TOPOLOGIES = {
    "baseline": baseline,
    "a_fast_mul": mod_a_fast_mul,
    "b_n_to_m": mod_b_n_to_m,
    "c_interleaved": mod_c_interleaved,
    "d_dma_per_pe": mod_d_dma_per_pe,
}


_DTYPES = {f: np.float32 if f in ("smul_power_scale", "t_clk_ns")
           else np.int32 for f in HwConfig.FIELDS}


def _stack_fields(configs) -> Dict[str, Any]:
    """Each field of ``configs`` as one ``(n,)`` array, float32 for
    ``smul_power_scale`` and ``t_clk_ns`` and int32 for the rest.

    The arrays are numpy, built on the host, so a caller expands the grid
    there and then moves each field to the device in one transfer
    (``stack_configs``, ``dse.plan_grid``, the service's
    ``_merge_plans``).  Where a configuration holds a tracer the fields
    are stacked with ``jnp`` inside the trace instead."""
    obs.COUNTS["hwconfig.configs_stacked"] += len(configs)
    with obs.span("hwconfig.stack", n=len(configs)):
        cols = {f: [getattr(c, f) for c in configs] for f in HwConfig.FIELDS}
        if any(isinstance(v, jax.core.Tracer)
               for col in cols.values() for v in col):
            return {f: jnp.stack([jnp.asarray(v, _DTYPES[f]) for v in col])
                    for f, col in cols.items()}
        return {f: np.asarray(col, _DTYPES[f]) for f, col in cols.items()}


def stack_configs(configs) -> HwConfig:
    """Stack a list of HwConfig into one batched HwConfig (leading axis) for
    vmap-based design-space sweeps: device arrays ``(n,)``, one transfer
    per field."""
    return jax.device_put(HwConfig(**_stack_fields(configs)))

"""Fleet-scale design-space exploration: the deployable version of the
paper's tool.

Sweeps the full (kernel program x hardware topology x data image) grid
through the fused simulate+estimate path in ONE call -- the programs are
packed to a common padded shape (`pack_programs`) and swept as data, so
G kernels cost one compile instead of G.  Vmapped, jitted, and (when
devices exist) mesh-sharded with shard_map.  On a 512-chip pod the same code
sweeps ~10^6 design points per compile; here it runs on whatever
jax.devices() shows.

Analysis runs ON DEVICE: instead of shipping the full (B,) result
arrays to the host and post-processing with argmin/reshape, the sweep
carries a ``reduce=`` spec (``analysis.pareto``) and only the O(G*K)
per-kernel candidate sets ever cross the device->host boundary -- a
million-point sweep ships kilobytes.  Candidates are tagged with their
flat grid index, so (kernel, hw, image) coordinates are recovered by
divmod.

  PYTHONPATH=src python examples/dse_sweep.py
"""
import time

import jax
import numpy as np

from repro.analysis.pareto import ParetoFront, TopK, reduced_nbytes
from repro.apps import mibench
from repro.core import dse
from repro.core.characterization import default_profile
from repro.core.hwconfig import TOPOLOGIES

profile = default_profile()

# program grid: four MiBench kernels of different lengths and characters
# (bit-twiddling, CRC polynomial division, image thresholding, hashing)
kernels = [mibench.bitcnt(), mibench.crc32(), mibench.susan_thresh(),
           mibench.sha_mix()]
programs = [k.program for k in kernels]
max_steps = max(k.max_steps for k in kernels)

# hardware grid: every topology x multiplier latency x bank count
hws = []
for mk in TOPOLOGIES.values():
    for smul_lat in (1, 3):
        for n_banks in (2, 8):
            hws.append(mk().replace(smul_lat=smul_lat, n_banks=n_banks))

# data grid: one image per kernel (the estimator is data-aware -- its
# edge over trace-driven models like CGRA-EAM); lane (g, h, d) runs
# program g on image d, so the g == d "diagonal" is each kernel on its
# own data and the off-diagonal lanes probe data sensitivity
mems = np.stack([k.mem_init for k in kernels])

G, H, D = len(programs), len(hws), len(mems)
B = G * H * D
mesh = jax.make_mesh((len(jax.devices()),), ("data",))

TOP_K = 3
topk_spec = TopK("energy_pj", k=TOP_K)
front_spec = ParetoFront(axes=("latency_cc", "energy_pj"), max_points=16)

t0 = time.time()
topk = dse.sweep(programs=programs, profile=profile, hw_configs=hws,
                 mem_images=mems, mesh=mesh, max_steps=max_steps,
                 reduce=topk_spec)
front = dse.sweep(programs=programs, profile=profile, hw_configs=hws,
                  mem_images=mems, mesh=mesh, max_steps=max_steps,
                  reduce=front_spec)
dt = time.time() - t0

full_bytes = B * 5 * 4                      # five (B,) 4-byte fields
red_bytes = reduced_nbytes(G, topk_spec) + reduced_nbytes(G, front_spec)
print(f"swept {G} kernels x {H} hw configs x {D} images = {B} design "
      f"points in {dt:.1f}s on {len(jax.devices())} device(s) "
      f"(ONE compiled executable per spec)")
print(f"device->host: {red_bytes} reduced bytes vs {full_bytes} for the "
      f"full grid ({full_bytes / red_bytes:.0f}x less)")


def coords(flat):
    """flat grid index -> (hw config, image) within a kernel's rows."""
    h, d = divmod(int(flat) % (H * D), D)
    return h, d


for g, k in enumerate(kernels):
    print(f"\n[{k.name}] top-{TOP_K} by energy:")
    for j in range(int(topk.count[g])):
        h, d = coords(topk.indices[g, j])
        print(f"  #{j + 1}: hw[{h}] image[{d}]  "
              f"latency {topk.latency_cc[g, j]:.0f} cc, "
              f"energy {topk.energy_pj[g, j] / 1e3:.2f} nJ  "
              f"({hws[h]})")
    n = int(front.count[g])
    # exact duplicates (several design points with identical latency and
    # energy) all sit on the front; print each distinct point once
    seen = dict.fromkeys(
        (f"({front.latency_cc[g, j]:.0f} cc, "
         f"{front.energy_pj[g, j] / 1e3:.2f} nJ)")
        for j in range(n))
    print(f"  latency/energy Pareto front ({n} points, "
          f"{len(seen)} distinct): {', '.join(seen)}")

"""Benchmark driver: one module per paper table/figure + system benches.

  PYTHONPATH=src python -m benchmarks.run [--only fig2,fig3,...]
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback

from . import (fig2_error_cases, fig3_conv_mappings, fig4_heatmap,
               fig5_hw_topology, roofline_table, sim_throughput)

ALL = {
    "fig2": fig2_error_cases,
    "fig3": fig3_conv_mappings,
    "fig4": fig4_heatmap,
    "fig5": fig5_hw_topology,
    "throughput": sim_throughput,
    "roofline": roofline_table,
}


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(ALL))
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    names = args.only.split(",") if args.only else list(ALL)
    failures = 0
    for name in names:
        mod = ALL[name]
        t0 = time.time()
        try:
            rep = mod.run()
            rep.print()
            print(f"[bench] {name} ok in {time.time()-t0:.1f}s")
        except Exception:
            failures += 1
            print(f"[bench] {name} FAILED:\n{traceback.format_exc()}")
    print(f"\n[bench] {len(names)-failures}/{len(names)} benchmarks ok")
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())

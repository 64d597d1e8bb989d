#!/usr/bin/env python3
"""Benchmark of the DSE sweep on the chip: one run of one cell.

    python3 bench/run.py --workload mibench_t2.sweep --seed 7 \\
        --seconds 30 --trace 0

Run from the root of a checkout.  The run loads, warms up the cell's own
shapes, measures for ``--seconds`` and checks a sample of what the
window produced against the plain reference (``benchlib/reference.py``).
Its last line on standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics,
or with ``--trace 1`` its per-layer metrics read from a profiler trace
of the window), ``device`` and, last, ``checks``: each compared number
beside its limit.  With no TPU, or fewer chips than the cell asks for,
it exits 2 and prints no result.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
    from benchlib.cell import load_cell
    from benchlib.session import prepare
    cell = load_cell(args.workload)
    tune = prepare(cell.chips)
    if tune is None:
        return 2
    try:
        from benchlib.drive import run_cell
        out = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                       T_START)
    finally:
        shutil.rmtree(tune, ignore_errors=True)
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/readings.py --workload conv_t2.sweep --seeds 12 \\
        --first-seed 4000000000 --seconds 8 [--control 3] [--out FILE]

In one process (set-up and compiles are paid once): a short window of
the cell's own traffic on each of ``--seeds`` seeds through the program,
then on the first ``--control`` of those seeds with the precision
control (the reference, its energy summed in bfloat16) in the program's
place.  Prints, and with ``--out`` writes, one JSON line per run with
the compared numbers; the benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import os
import shutil
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--control", type=int, default=3)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
    from benchlib.cell import load_cell
    from benchlib.drive import control_engine, run_cell
    from benchlib.session import prepare
    cell = load_cell(args.workload)
    tune = prepare(cell.chips)
    if tune is None:
        return 2
    rows = []
    try:
        runs = [("program", s, None) for s in range(args.seeds)]
        runs += [("control", s, control_engine(cell))
                 for s in range(args.control)]
        for who, i, engine in runs:
            seed = args.first_seed + i
            out = run_cell(cell, seed, args.seconds, False,
                           time.perf_counter(), engine=engine)
            row = {"who": who, "seed": seed, "correct": out["correct"],
                   "attempted": out["attempted"], "failed": out["failed"],
                   **{k: v["value"] for k, v in out["checks"].items()}}
            rows.append(row)
            print(json.dumps(row), flush=True)
    finally:
        shutil.rmtree(tune, ignore_errors=True)
    if args.out:
        with open(args.out, "w") as f:
            for r in rows:
                f.write(json.dumps(r) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

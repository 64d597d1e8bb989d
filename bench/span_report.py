#!/usr/bin/env python3
"""Where the host's time goes, by the program's own spans, on the chip.

    python3 bench/span_report.py --workload mibench_t2.served \\
        --seed 4000000001 --seconds 20 [--out FILE]

One process: set-up as ``bench/run.py`` makes it, then one window of
the cell's traffic traced with the profiler, reduced by
``benchlib.program_spans``.  Prints, and with ``--out`` writes, one JSON
line: the traced end-to-end metrics, the cell's per-layer metrics and
those that read the program's spans and counters, the device's idle
gaps by program span, and the span events per campaign or request.
The window's answers are not checked.  Exits 2 without the cell's
chips.
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
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)

    sys.path.insert(0, BENCH)
    sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))
    from benchlib.cell import load_cell
    from benchlib.program_spans import measure
    from benchlib.session import prepare
    cell = load_cell(args.workload)
    tune = prepare(cell.chips)
    if tune is None:
        return 2
    try:
        out = measure(cell, args.seed, args.seconds, T_START)
    finally:
        shutil.rmtree(tune, ignore_errors=True)
    line = json.dumps(out)
    print(line, flush=True)
    if args.out:
        with open(args.out, "a") as f:
            f.write(line + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

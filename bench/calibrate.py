#!/usr/bin/env python3
"""Readings for the limits of ``correct``: one cell, many seeds, one process.

    python3 bench/calibrate.py --workload glm4-9b-pp2.short-chat \\
        --seeds 101,102,103 --seconds 8 [--control 1] [--out FILE]

Each seed is a whole run of the cell (weights, prompts, window, check) as
``bench/run.py`` makes it, on the chip; with ``--control 1`` the fp8
control stands in the program's place in the check, so ``correct`` is the
control's, and the program's own gap is printed beside it.  Prints one
line per seed and writes all results as JSON to ``--out``.  The limits
file takes its numbers from here, by the rule in PERF.md; the benchmark's
own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path[:1] = [str(Path(__file__).resolve().parents[1])]

from bench import run as bench_run  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=None)
    args = ap.parse_args()
    bench_run.prepare()
    from bench import spec

    cell = spec.load_cell(bench_run.ROOT, args.workload)
    bench_run.require_chips(cell["workload"]["chips"])
    results = []
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = bench_run.run_cell(cell, seed, args.seconds, bool(args.trace),
                                 time.perf_counter(), control=bool(args.control))
        out["seed"] = seed
        results.append(out)
        print(f"[calibrate] seed {seed}: correct={out['correct']} "
              f"checks={json.dumps(out['checks'])} "
              f"program_widest_gap={out.get('program_widest_gap')} "
              f"metrics={json.dumps(out['metrics'])} "
              f"peak={out['device']['memory_peak_bytes']}", flush=True)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(json.dumps(results, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Benchmark harness — one module per paper table/claim, on the host.

Prints ``name,us_per_call,derived`` CSV.  Modules:

  lock_ops      — RDMA-op cost claims (paper §3.1)         [the paper's table]
  lock_compare  — throughput/fairness vs naive/RPC/filter  (paper §1, §3, §4)
  lock_table_bench — sharded table: scaling, fairness, hot-path fast paths
  collectives   — cohort vs flat DCN traffic               (TPU adaptation)

The model's steps and kernels are timed on the chip by ``bench/run.py``.

``--json OUT`` additionally writes each module's results to
``OUT/BENCH_<name>.json`` (default OUT: the repo root), the machine-readable
perf trajectory.  A module may expose ``BENCH_NAME`` (file-name stem) and
``json_extra()`` (rich payload merged into its record, e.g. the lock table's
before/after comparison).
"""

import argparse
import json
import pathlib
import sys
import traceback

REPO_ROOT = pathlib.Path(__file__).resolve().parent.parent


def main() -> None:
    ap = argparse.ArgumentParser(description="run all benchmarks")
    ap.add_argument(
        "--json", metavar="OUT", nargs="?", const=str(REPO_ROOT), default=None,
        help="write BENCH_<name>.json per module into OUT (default: repo root)",
    )
    args = ap.parse_args()
    rows = []

    from . import collectives, lock_compare, lock_ops, lock_table_bench

    failures = []
    for mod in (lock_ops, lock_compare, lock_table_bench, collectives):
        mod_rows = []

        def report(name, us_per_call, derived="", _rows=mod_rows):
            rows.append((name, us_per_call, derived))
            _rows.append({"name": name, "us_per_call": us_per_call,
                          "derived": derived})
            print(f"{name},{us_per_call:.3f},{derived}")

        try:
            mod.run(report)
        except Exception:
            traceback.print_exc()
            failures.append(mod.__name__)
            continue
        if args.json:
            name = getattr(mod, "BENCH_NAME", mod.__name__.rsplit(".", 1)[-1])
            payload = {"bench": name, "rows": mod_rows}
            extra = getattr(mod, "json_extra", None)
            if extra is not None:
                payload.update(extra())
            out = pathlib.Path(args.json) / f"BENCH_{name}.json"
            out.parent.mkdir(parents=True, exist_ok=True)
            out.write_text(json.dumps(payload, indent=2, sort_keys=True) + "\n")
            print(f"# wrote {out}")
    if failures:
        print(f"BENCHMARK FAILURES: {failures}", file=sys.stderr)
        sys.exit(1)
    print(f"# {len(rows)} benchmark rows")


if __name__ == "__main__":
    main()

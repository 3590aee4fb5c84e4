#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload glm4-9b-pp2.short-chat --seed 7 \\
        --seconds 30 --trace 0

Prints, as the last line of stdout, one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``, with
``--trace 1`` a ``breakdown``, and last ``checks``: each number ``correct``
compares beside its limit, which are also the last lines of stderr.  Exits
non-zero and prints no result when JAX finds no TPU or fewer chips than the
cell asks for.  JAX's persistent compilation cache lives in ``.jax_cache``
at the root of the checkout, so only a cell's first run there compiles.
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
CACHE_DIR = ROOT / ".jax_cache"


def prepare() -> None:
    """Import paths, and JAX's persistent compilation cache at a fixed
    directory of the checkout, which the program's compile_cache module
    takes from the environment; every program is cached, however fast."""
    if sys.path and Path(sys.path[0]).resolve() == ROOT / "bench":
        sys.path.pop(0)  # import bench.* as a package, never as top level
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # no logs under /tmp
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


def require_chips(chips: int) -> None:
    """Exit non-zero, before any result, unless JAX finds at least
    ``chips`` TPUs."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: JAX found no TPU (platform {devs[0].platform!r}); no result")
    if len(devs) < chips:
        sys.exit(f"bench: the cell needs {chips} TPU chips, JAX found {len(devs)}")


def run_cell(cell: dict, seed: int, seconds: float, trace: bool,
             t_process: float, control: bool = False) -> dict:
    """One run of a cell, past the look for a chip: the driver its traffic
    kind names (``bench/<kind>.py``), the metrics, the checks."""
    import jax

    from bench import spec

    driver = importlib.import_module(f"bench.{cell['traffic']['kind']}")
    rec = driver.run(cell, seed, seconds, trace, t_process, control=control)
    d = jax.devices()[0]
    rec["peaks"], rec["device_kind"] = cell["peaks"], d.device_kind
    metrics = spec.read_metrics(
        cell["root"], cell["per_layer"] if trace else cell["end_to_end"], rec)
    limits = cell["limits"]
    checks = {k: {"value": v, "limit": limits[k]} for k, v in rec["checks"].items()}
    correct = all(c["value"] <= c["limit"] for c in checks.values())
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(jax.devices()),
              "memory_peak_bytes": rec["memory_peak_bytes"]}
    out = {"correct": correct, "attempted": rec["attempted"],
           "failed": rec["failed"], "metrics": metrics, "device": device}
    if trace:
        device["busy_s"] = rec["trace"]["busy_s"]
        device["window_s"] = rec["trace"]["window_s"]
        out["breakdown"] = rec["trace"]["breakdown"]
    if control:
        out["program_widest_gap"] = rec["program_gap"]
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="put the fp8 control in the program's place in the "
                         "check (calibration only; the benchmark's own runs "
                         "leave it off)")
    args = ap.parse_args(argv)

    prepare()
    from bench import spec

    cell = spec.load_cell(ROOT, args.workload)
    require_chips(cell["workload"]["chips"])
    out = run_cell(cell, args.seed, args.seconds, bool(args.trace), T_PROCESS,
                   control=bool(args.control))
    sys.stdout.flush()
    for name, c in out["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

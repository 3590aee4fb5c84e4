"""Find a cell's files by the names in ``BENCHMARK.json``.

A cell names a configuration and a traffic mix; a metric names its reader.
Each lives in a file of its own, looked up under ``<root>/bench/<kind>/``
first and then beside this module, so a cell, a mix, a configuration, a
reference family or a metric is added by adding files alone:

    configs/<config>.json      the configuration as run (its ``file`` entry)
    traffic/<traffic>.json     the mix's parameters
    reference/<family>.py      the plain reference the configuration names
    metrics/<metric>.py        ``read(record) -> float | None``
    limits/<workload>.json     the limit of each number ``correct`` compares
"""

from __future__ import annotations

import importlib.util
import json
from pathlib import Path
from typing import Dict, List

HERE = Path(__file__).resolve().parent


def find(root: Path, kind: str, name: str, ext: str) -> Path:
    for base in (Path(root) / "bench", HERE):
        p = base / kind / f"{name}{ext}"
        if p.is_file():
            return p
    raise FileNotFoundError(f"no {kind}/{name}{ext} under {root}/bench or {HERE}")


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        f"bench_{path.parent.name}_{path.stem.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(entry: Dict, workload: str) -> bool:
    return "workloads" not in entry or workload in entry["workloads"]


def load_cell(root: Path, workload: str) -> Dict:
    """Everything one run of ``workload`` needs, read from files."""
    root = Path(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = next(c for c in bench["configs"] if c["name"] == w["config"])
    cfg = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(find(root, "traffic", w["traffic"], ".json").read_text())
    limits = json.loads(find(root, "limits", workload, ".json").read_text())
    end_to_end: List[Dict] = [m for m in bench["end_to_end"] if _applies(m, workload)]
    per_layer: List[Dict] = [m for m in bench["per_layer"] if _applies(m, workload)]
    return {"root": root, "workload": w, "config": cfg, "traffic": traffic,
            "limits": limits, "end_to_end": end_to_end, "per_layer": per_layer,
            "family": load_module(find(root, "reference", cfg["family"], ".py")),
            "peaks": json.loads((HERE / "peaks.json").read_text())}


def read_metrics(root: Path, entries: List[Dict], record: Dict) -> Dict:
    """Run each metric's reader; a reader that finds nothing returns None
    and the metric is left out."""
    out = {}
    for m in entries:
        value = load_module(find(root, "metrics", m["name"], ".py")).read(record)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out

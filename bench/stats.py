"""Small helpers the metric readers share."""

from __future__ import annotations

from typing import Dict, List, Optional

import numpy as np


def p95(values: List[float]) -> Optional[float]:
    """95th percentile (linear interpolation), or None with no values."""
    return float(np.percentile(values, 95)) if values else None


def window_end(rec: Dict) -> float:
    return rec["t0"] + rec["seconds"]


def served(rec: Dict) -> bool:
    return rec.get("kind") == "serve"


def module_durations(rec: Dict, phase: str) -> List[float]:
    """Device seconds of each traced call of the program behind ``phase``."""
    if not rec.get("trace"):
        return []
    return rec["trace"]["modules"].get(rec["module_names"][phase], [])


def peak(rec: Dict) -> Dict:
    """The chip's peaks; a device missing from ``peaks.json`` is an error."""
    return rec["peaks"][rec["device_kind"]]


def decode_contexts(rec: Dict, n: int) -> List[int]:
    """Cached tokens before each of ``n`` traced decode calls: the traced
    batches are whole, so call k of a batch runs with prompt_len + k."""
    t = rec["traffic"]
    per = t["gen_len"] - 1
    return [t["prompt_len"] + k % per for k in range(n)]

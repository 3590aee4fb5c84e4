"""From a profiler trace to the numbers the per-layer readers take.

``read_xspace`` turns the ``.xplane.pb`` that ``jax.profiler`` writes into a
small dict of events (device modules and operations of chip 0, and the
benchmark's own ``bench.*`` host spans), all in nanoseconds on the trace's
clock.  ``reduce_events`` turns that dict into seconds: the traced window,
the device's busy time in it, each program's device durations, and the
``breakdown`` of the result line.  The two steps are apart so that tests run
the reduction on a small recorded trace committed beside them.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict
from typing import Dict, List, Sequence, Tuple

SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.batch"
Event = Tuple[str, float, float]  # (name, start_ns, duration_ns)


def read_xspace(trace_dir: str, device_plane: str = "/device:TPU:0") -> Dict:
    """Events of ``device_plane`` and the host's ``bench.*`` spans."""
    from jax.profiler import ProfileData

    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {trace_dir}, found {paths}")
    pd = ProfileData.from_file(paths[0])
    out: Dict[str, List[Event]] = {"modules": [], "ops": [], "spans": []}
    for plane in pd.planes:
        if plane.name == device_plane:
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(line.name)
                if key:
                    out[key] += [(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["spans"] += [(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events
                                 if e.name.startswith(SPAN_PREFIX)]
    return out


def merge(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float, float]]:
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def clip(intervals, lo: float, hi: float) -> List[Tuple[float, float]]:
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def module_base(name: str) -> str:
    """``jit_decode(12345)`` → ``jit_decode``."""
    return re.sub(r"\(\d+\)$", "", name)


CONTAINER_OPS = ("while", "conditional", "call")


def op_label(text: str) -> str:
    """``%fusion.147 = bf16[16,256,27392]{2,1,0:T(8,128)} fusion(...)...`` →
    ``fusion.147 bf16[16,256,27392] fusion``: the op's name, result type
    and kind, without layouts or operands."""
    name, _, rhs = text.lstrip("%").partition(" = ")
    op = re.search(r"\s([a-z][a-z0-9\-]*)\(", rhs)
    if not op:
        return text[:120]
    shape = re.sub(r"\{[^{}]*\}|/\*[^*]*\*/", "", rhs[:op.start()])
    return f"{name} {shape[:60]} {op.group(1)}"


def _innermost(spans: List[Event], t: float) -> str:
    best, best_dur = "untraced host work", float("inf")
    for name, s, d in spans:
        if s <= t <= s + d and d < best_dur and name != WINDOW_SPAN:
            best, best_dur = name, d
    return best


def reduce_events(ev: Dict, top: int = 10) -> Dict:
    """Seconds from a trace's events.

    The window runs from the start of the first ``bench.batch`` span to the
    end of the last, widened to the device programs that overlap them.  Busy time is the union of device operations inside it
    (of device modules, where the trace has no operation line).  Idle gaps
    are the holes in that union, each named by the innermost ``bench.*``
    span that holds the gap's midpoint, and summed per name.
    """
    batches = [(s, s + d) for n, s, d in ev["spans"] if n == WINDOW_SPAN]
    if not batches:
        raise RuntimeError("trace holds no bench.batch span")
    lo, hi = min(s for s, _ in batches), max(e for _, e in batches)
    # The device's clock is mapped onto the host's to within about a
    # millisecond, so a batch's first program can start "before" its span:
    # the window takes in every program that overlaps the spans.
    inside = sorted((e for e in ev["modules"] if e[1] < hi and e[1] + e[2] > lo),
                    key=lambda e: e[1])
    if inside:
        lo = min(lo, inside[0][1])
        hi = max(hi, max(s + d for _, s, d in inside))
    work = ev["ops"] or ev["modules"]
    busy = merge(clip([(s, s + d) for _, s, d in work], lo, hi))
    busy_ns = sum(e - s for s, e in busy)

    modules: Dict[str, List[float]] = defaultdict(list)
    for name, _, d in inside:
        modules[module_base(name)].append(d * 1e-9)

    # Ops that hold others (a scan's while loop) would count their body twice.
    op_time: Dict[str, float] = defaultdict(float)
    for name, s, d in ev["ops"]:
        label = op_label(name)
        if lo <= s < hi and label.rsplit(" ", 1)[-1] not in CONTAINER_OPS:
            op_time[label] += d * 1e-9

    gaps: Dict[str, float] = defaultdict(float)
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            gaps[_innermost(ev["spans"], (a + b) / 2)] += (b - a) * 1e-9

    rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]  # noqa: E731
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": busy_ns * 1e-9,
        "modules": dict(modules),
        "breakdown": {"device_ops": [list(kv) for kv in rank(op_time)],
                      "idle_gaps": [list(kv) for kv in rank(gaps)]},
    }

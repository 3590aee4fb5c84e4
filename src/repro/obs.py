"""The program's spans and counters.

One bounded in-memory ring of ``(name, start_ns, end_ns, attrs)`` tuples,
all on ``time.perf_counter_ns()``:

* :class:`span` is a context manager around a piece of host work.  While a
  profiler trace is being collected it also enters
  ``jax.profiler.TraceAnnotation(name)``, so the span lands on the trace's
  timeline beside the device's ops.
* :func:`start` and :func:`end` do the same for a hot path that runs cold
  between device steps (admission): a tuple in place of an object and two
  plain calls in place of the context-manager protocol.
* :func:`record` writes a span that is reported after the fact (a
  compile).
* Counters ride as span attributes, so every number has a time and a
  place: ``with span("x") as s: ...; s.attrs["n"] = n``.

Readers take :func:`spans` (filtered by name prefix and time) as
:class:`Span` records, and map ring times onto the wall clock with
:data:`CLOCK_PAIR`.  Spans are always on, so callers keep them off
per-step paths that must stay lean: a span costs about a microsecond of
Python in a hot loop, several when the loop runs its code cold between
device steps.

Importing the module registers one JAX listener: every backend compile or
persistent-cache load becomes a ``repro.compile`` record carrying the
compiled function's ``fun_name``.
"""

from __future__ import annotations

import collections
import time
from typing import Dict, List, NamedTuple, Optional, Tuple

import jax

RING_SIZE = 65_536
COMPILE_SPAN = "repro.compile"
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: ``(time.time_ns(), time.perf_counter_ns())`` read once at import: a ring
#: time ``t`` lies at wall time ``CLOCK_PAIR[0] + t - CLOCK_PAIR[1]``.
CLOCK_PAIR = (time.time_ns(), time.perf_counter_ns())

_now = time.perf_counter_ns
_TraceAnnotation = jax.profiler.TraceAnnotation
_tracing = _TraceAnnotation.is_enabled
_ring: collections.deque = collections.deque(maxlen=RING_SIZE)


class Span(NamedTuple):
    """One entry of the ring, as :func:`spans` returns it."""

    name: str
    start_ns: int
    end_ns: int
    attrs: Dict

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


def start(name: str) -> Tuple:
    """Open a span by hand, for a caller that times its own hot path: hand
    what this returns to :func:`end`.  Enters the profiler annotation while
    a profiler collects."""
    if _tracing():
        a = _TraceAnnotation(name)
        a.__enter__()
    else:
        a = None
    return name, a, _now()


def end(opened: Tuple, **attrs) -> int:
    """Close a span that :func:`start` opened, write it to the ring with
    ``attrs``, and return its end (ring clock)."""
    end_ns = _now()
    name, a, start_ns = opened
    if a is not None:
        a.__exit__(None, None, None)
    _ring.append((name, start_ns, end_ns, attrs))
    return end_ns


class span:
    """Times its body as ``name``, raised or not, and writes it to the ring
    as it leaves, with ``attrs`` and whatever the body adds to ``.attrs``."""

    __slots__ = ("name", "attrs", "start_ns", "end_ns", "_opened")

    def __init__(self, name: str, **attrs):
        self.name, self.attrs = name, attrs

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9

    def __enter__(self) -> "span":
        self._opened = start(self.name)
        self.start_ns = self._opened[2]
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        self.end_ns = end(self._opened, **self.attrs)


def record(name: str, start_ns: int, end_ns: int, **attrs) -> None:
    """Write a span timed elsewhere (ring clock) to the ring only."""
    _ring.append((name, start_ns, end_ns, attrs))


def spans(name_prefix: Optional[str] = None, since_ns: Optional[int] = None,
          until_ns: Optional[int] = None) -> List[Span]:
    """Ring entries in the order they were written (a span as it closes):
    those whose name starts with ``name_prefix``, that start at or after
    ``since_ns`` and end by ``until_ns``."""
    ring = _ring.copy()  # one C call: safe against appends
    return [Span._make(s) for s in ring
            if (name_prefix is None or s[0].startswith(name_prefix))
            and (since_ns is None or s[1] >= since_ns)
            and (until_ns is None or s[2] <= until_ns)]


def reset() -> None:
    _ring.clear()


def _on_compile(event: str, start_time: float, end_time: float, **kw) -> None:
    # JAX times the compile on time.time(); the listener runs as it ends,
    # so the ring's clock is read now and the duration carried back.
    if event == _COMPILE_EVENT:
        end_ns = _now()
        record(COMPILE_SPAN, end_ns - round((end_time - start_time) * 1e9),
               end_ns, fun_name=kw.get("fun_name"))


jax.monitoring.register_event_time_span_listener(_on_compile)

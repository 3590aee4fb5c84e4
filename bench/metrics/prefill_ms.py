"""prefill_ms: device time of one call of the prefill program, mean over
the traced calls (device trace, module events)."""

from bench.stats import module_durations


def read(rec):
    d = module_durations(rec, "prefill")
    return sum(d) / len(d) * 1e3 if d else None

"""compiles_in_window: backend compiles and persistent-cache loads that
ended inside the measured window (program counter: ``repro.compile``
records).  0 when every shape was warmed up in set-up.  None for a program
that does not record them."""

from bench.stats import window_end


def read(rec):
    try:
        from repro import obs
    except ImportError:
        return None
    t0, end = int(rec["t0"] * 1e9), int(window_end(rec) * 1e9)
    return float(sum(1 for s in obs.spans("repro.compile", until_ns=end)
                     if s.end_ns >= t0))

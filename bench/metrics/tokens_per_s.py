"""tokens_per_s: output tokens that reached the host inside the window,
over the window's seconds (host clock)."""

from bench.stats import served, window_end


def read(rec):
    if not served(rec):
        return None
    end = window_end(rec)
    n = sum(rec["traffic"]["batch"] for b in rec["batches"]
            for t in b["times"] if t <= end)
    return n / rec["seconds"]

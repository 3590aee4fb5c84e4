"""ttft_p95_ms: 95th percentile over the requests of the window of the time
from their batch's admit call to their first token on the host."""

from bench.stats import p95, served, window_end


def read(rec):
    if not served(rec):
        return None
    end = window_end(rec)
    vals = []
    for b in rec["batches"]:
        if b["times"] and b["times"][0] <= end:
            vals += [(b["times"][0] - b["t_admit"]) * 1e3] * rec["traffic"]["batch"]
    return p95(vals)

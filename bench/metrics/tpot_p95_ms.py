"""tpot_p95_ms: 95th percentile over the requests finished inside the
window of the time per output token after the first, (last token - first
token) / (gen_len - 1) on the host clock; each spans many decode steps."""

from bench.stats import p95, served, window_end


def read(rec):
    if not served(rec):
        return None
    end = window_end(rec)
    vals = []
    for b in rec["batches"]:
        if b["finished"] and b["times"][-1] <= end:
            per = (b["times"][-1] - b["times"][0]) / (len(b["times"]) - 1)
            vals += [per * 1e3] * rec["traffic"]["batch"]
    return p95(vals)

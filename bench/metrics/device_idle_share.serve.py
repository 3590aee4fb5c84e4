"""device_idle_share.serve: the share of the traced serving window in which
no operation ran on the device, in percent (device trace)."""

from bench.stats import served


def read(rec):
    if not served(rec) or not rec.get("trace"):
        return None
    return 100.0 * (1.0 - rec["trace"]["busy_s"] / rec["trace"]["window_s"])

"""admission_span_us_per_batch: the program's own time in admission per
batch (program spans): the durations of the ``admission.admit``,
``admission.keepalive`` and ``admission.complete`` spans of each lease
completed in the window, summed per lease, mean over those leases.  The
inside twin of admission_us_per_batch, which times the same calls from the
benchmark's loop.  None for a program without these spans."""

from collections import defaultdict

from bench.stats import served, window_end

CALLS = ("admission.admit", "admission.keepalive", "admission.complete")


def read(rec):
    try:
        from repro import obs
    except ImportError:
        return None
    if not served(rec):
        return None
    per_lease, done = defaultdict(int), set()
    for s in obs.spans("admission.", since_ns=int(rec["t0"] * 1e9),
                       until_ns=int(window_end(rec) * 1e9)):
        if s.name in CALLS:
            lease = (s.attrs.get("key"), s.attrs.get("token"))
            per_lease[lease] += s.end_ns - s.start_ns
            if s.name == "admission.complete":
                done.add(lease)
    return sum(per_lease[k] for k in done) / len(done) * 1e-3 if done else None

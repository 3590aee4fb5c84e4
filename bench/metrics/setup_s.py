"""setup_s: process start to the first timed batch (host clock): weights,
lowering, compile-cache loads and warm-up."""


def read(rec):
    return rec["setup_s"]

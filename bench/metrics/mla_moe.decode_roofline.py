"""mla_moe.decode_roofline: the least time the chip could take for the
traced decode calls over their device time, in percent, by the counts of
``bench/reference/mla_moe.py``: the held experts' weights, read by the
grouped products, are most of a step's bytes.  The generic
``decode_roofline`` reader, loaded, not copied."""

from pathlib import Path

from bench import spec

_GENERIC = spec.load_module(spec.find(
    Path(__file__).resolve().parents[2], "metrics", "decode_roofline", ".py"))


def read(rec):
    return _GENERIC.read(rec)

"""mla_moe.mfu.prefill: model FLOPs of the traced prefill calls (counted by
``bench/reference/mla_moe.py``) over their device time times the chip's
peak, in percent; at about 3,000 rows per held expert the grouped products
are bound by compute.  The generic ``mfu.prefill`` reader, loaded, not
copied."""

from pathlib import Path

from bench import spec

_GENERIC = spec.load_module(spec.find(
    Path(__file__).resolve().parents[2], "metrics", "mfu.prefill", ".py"))


def read(rec):
    return _GENERIC.read(rec)

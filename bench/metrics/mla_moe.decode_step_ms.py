"""mla_moe.decode_step_ms: device time of one call of the decode program
(absorbed latent attention and the expert-share layer), mean over the
traced calls: the generic ``decode_step_ms`` reader, loaded, not copied."""

from pathlib import Path

from bench import spec

_GENERIC = spec.load_module(spec.find(
    Path(__file__).resolve().parents[2], "metrics", "decode_step_ms", ".py"))


def read(rec):
    return _GENERIC.read(rec)

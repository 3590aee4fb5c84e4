"""mfu.decode: model FLOPs of the traced decode calls (counted from shapes
by the reference family) over their device time times the chip's peak
FLOP/s, in percent."""

from bench.stats import decode_contexts, module_durations, peak


def read(rec):
    d = module_durations(rec, "decode")
    if not d:
        return None
    fam, cfg, B = rec["family"], rec["config"], rec["traffic"]["batch"]
    flops = sum(fam.decode_cost(cfg, B, c)[0] for c in decode_contexts(rec, len(d)))
    return 100.0 * flops / (sum(d) * peak(rec)["bf16_flops_per_s"])

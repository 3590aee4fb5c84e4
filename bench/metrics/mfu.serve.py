"""mfu.serve: model FLOPs of every traced prefill and decode call (counted
from shapes by the reference family) over the traced window times the
chip's peak FLOP/s, in percent."""

from bench.stats import decode_contexts, module_durations, peak


def read(rec):
    if not rec.get("trace"):
        return None
    t, fam, cfg = rec["traffic"], rec["family"], rec["config"]
    pre, dec = module_durations(rec, "prefill"), module_durations(rec, "decode")
    flops = len(pre) * fam.prefill_cost(cfg, t["batch"], t["prompt_len"])[0]
    flops += sum(fam.decode_cost(cfg, t["batch"], c)[0]
                 for c in decode_contexts(rec, len(dec)))
    return 100.0 * flops / (rec["trace"]["window_s"] * peak(rec)["bf16_flops_per_s"])

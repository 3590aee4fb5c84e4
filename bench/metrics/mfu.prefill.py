"""mfu.prefill: model FLOPs of the traced prefill calls (counted from shapes
by the reference family) over their device time times the chip's peak
FLOP/s, in percent."""

from bench.stats import module_durations, peak


def read(rec):
    d = module_durations(rec, "prefill")
    if not d:
        return None
    t = rec["traffic"]
    flops = rec["family"].prefill_cost(rec["config"], t["batch"], t["prompt_len"])[0]
    return 100.0 * flops * len(d) / (sum(d) * peak(rec)["bf16_flops_per_s"])

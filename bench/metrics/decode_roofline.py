"""decode_roofline: the least time the chip could take for the traced
decode calls over their device time, in percent.  Bytes per step: every
weight but the embedding table, B embedding rows, the valid cache at its
key/value heads or the recurrent state read and written (counted from
shapes by the reference family); the byte bound binds."""

from bench.stats import decode_contexts, module_durations, peak


def read(rec):
    d = module_durations(rec, "decode")
    if not d:
        return None
    pk, fam, cfg = peak(rec), rec["family"], rec["config"]
    bound = 0.0
    for ctx in decode_contexts(rec, len(d)):
        flops, nbytes = fam.decode_cost(cfg, rec["traffic"]["batch"], ctx)
        bound += max(flops / pk["bf16_flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * bound / sum(d)

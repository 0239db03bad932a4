"""Model FLOPs of every token the window prefilled or decoded, once
(matrix products plus attention over its context; a GRPO group's shared
prompt counts once), over the window's seconds times the bf16 peak."""
import flops as F


def read(rec):
    c = rec.counts
    if "rounds" not in c or rec.window_s <= 0:
        return None
    work = (F.forward_flops(rec.config, c["tokens"], c["decode_keys"])
            + F.forward_flops(rec.config, c["prefill_tokens"],
                              c["prefill_keys"]))
    return 100.0 * work / (rec.window_s * rec.peak["bf16_flops"]
                           * rec.device["count"])

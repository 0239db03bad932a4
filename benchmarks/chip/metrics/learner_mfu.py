"""6 x matmul params x non-pad tokens, plus causal attention (three times
its forward FLOPs), with no recomputation counted, over the window's
seconds times the bf16 peak."""
import flops as F


def read(rec):
    c = rec.counts
    if "steps" not in c or rec.window_s <= 0:
        return None
    work = F.train_flops(rec.config, c["tokens"], c["keys"])
    return 100.0 * work / (rec.window_s * rec.peak["bf16_flops"]
                           * rec.device["count"])

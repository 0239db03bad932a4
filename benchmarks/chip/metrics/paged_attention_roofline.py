"""The paged decode kernel's share of its roofline: the least time its
work needs over the device time of its events.  The work is the live K/V
of the rows that decoded, step by step (flops.decode_keys from each
row's length before the round and its token count), never the table
width or the grid the kernel walks."""
import flops as F


def read(rec):
    if rec.trace is None or not rec.counts.get("decode_keys"):
        return None
    t = rec.trace.op_time("paged_attention")
    if t <= 0:
        return None
    least = F.paged_attention_least_s(rec.config, rec.counts["decode_keys"],
                                      rec.peak)
    return 100.0 * least / t

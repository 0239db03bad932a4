"""Non-pad trajectory tokens stepped in the window over the window's
seconds (host clock, the window ending on block_until_ready)."""


def read(rec):
    if "steps" not in rec.counts or rec.window_s <= 0:
        return None
    return rec.counts["tokens"] / rec.window_s

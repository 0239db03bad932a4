"""Model tokens sampled in the window over the window's seconds (host
clock): every token of every round the window holds."""


def read(rec):
    if "rounds" not in rec.counts or rec.window_s <= 0:
        return None
    return rec.counts["tokens"] / rec.window_s

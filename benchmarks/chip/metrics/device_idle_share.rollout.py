"""1 - union of device op intervals / traced window, averaged over the
chips used (rollout cells)."""


def read(rec):
    if rec.trace is None or ("rounds" not in rec.counts):
        return None
    return 100.0 * (1.0 - rec.trace.busy_s / rec.trace.window_s)

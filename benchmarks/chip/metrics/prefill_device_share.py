"""Share of the traced window the device spent in the prefill programs
(prompt, observation and swap-in prefills through extend)."""


def read(rec):
    if rec.trace is None or "rounds" not in rec.counts:
        return None
    t = rec.trace.module_time("prefill")
    if t <= 0:
        return None
    return 100.0 * t / (rec.trace.window_s * rec.trace.n_devices)

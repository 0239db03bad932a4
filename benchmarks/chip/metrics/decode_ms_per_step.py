"""Device milliseconds of the fused decode-loop programs per decode step
they ran (loop iterations, counted from each round's token counts)."""


def read(rec):
    if rec.trace is None or not rec.counts.get("decode_steps"):
        return None
    t = rec.trace.module_time("decode_loop")
    if t <= 0:
        return None
    return 1000.0 * t / rec.counts["decode_steps"]

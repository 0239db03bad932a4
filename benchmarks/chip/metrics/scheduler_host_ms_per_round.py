"""Milliseconds per scheduler round spent outside engine calls: the
window's host-clock length minus the time inside the benchmark's spans
around engine.start / extend_rows / reset_rows / generate, per round."""


def read(rec):
    c = rec.counts
    if not c.get("rounds"):
        return None
    return 1000.0 * (rec.window_s - c["engine_s"]) / c["rounds"]

"""A metric reader of the kind a configuration's own PR adds beside its
program code: it reads a counter (``engine/fixture_widgets``) and a host
span (``fixture/step``) that the benchmark's code never names, both
through the record alone.  Milliseconds of ``fixture/step`` per widget
counted in the window."""
import harness as H


def read(rec):
    n = H.program_delta(rec, "engine/fixture_widgets")
    t = rec.trace.span_s.get("fixture/step") if rec.trace else None
    if not n or not t:
        return None
    return 1000.0 * t / n

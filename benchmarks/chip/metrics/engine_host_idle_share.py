"""Share of the traced window the device sat idle while the innermost
host span open was one of the engine's own (``engine/*``: alloc, cow,
sync_tables, prefill, ...), from the idle gaps the trace names."""


def read(rec):
    t = rec.trace
    if t is None or not any(k.startswith("engine/") for k in t.span_s):
        return None
    idle = sum(sec for name, sec in t.gaps if name.startswith("engine/"))
    return 100.0 * idle / t.window_s

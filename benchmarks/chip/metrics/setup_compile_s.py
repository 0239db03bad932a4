"""The program's own compile seconds from process start to the window's
opening: its ``compile/*_s`` counters at the opening (tracing, lowering,
XLA compilation and loads from the compile cache, each event credited
with its own seconds, so none counted twice)."""

KEYS = ("compile/trace_s", "compile/lower_s", "compile/backend_s",
        "compile/cache_load_s")


def read(rec):
    opened = rec.counts.get("program_open", {})
    if not any(k in opened for k in KEYS):
        return None
    return sum(opened.get(k, 0.0) for k in KEYS)

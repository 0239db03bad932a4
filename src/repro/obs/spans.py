"""Host spans on the profiler's clock, and compile counters.

:class:`span` is the one primitive for timing host work.  Each span always
opens a ``jax.profiler.TraceAnnotation`` of the same name, which records
nothing unless a profiler session is running — so a ``jax.profiler`` trace
(an operator's, or a benchmark's traced window) shows the program's own
steps on the device trace's clock.  When the :class:`SpanTracer` is on,
the span is also a complete event of the Chrome export: on the track named
by the span's namespace (``engine/prefill`` -> track ``engine``, event
``prefill``) unless the call site names another.

Spans nest per thread; :func:`current_span` is the innermost one open.
Never open a span inside a jitted or traced function, and open one span
per call, not one per row of a per-row loop.

One ``jax.monitoring`` listener per process turns JAX's compile events
into registry counters:

* ``compile/trace_s``      — jaxpr tracing (``jaxpr_trace_duration``);
* ``compile/lower_s``      — lowering to MLIR
  (``jaxpr_to_mlir_module_duration``);
* ``compile/backend_s``    — XLA compilation (``backend_compile_duration``);
* ``compile/cache_load_s`` — loads from the persistent compilation cache
  (``cache_retrieval_time_sec``);
* ``compile/programs``     — backend compile events (compiled or loaded).

These events nest: a cache load runs inside its backend compile, and a
jit traced while another is being traced (or an eager op run during a
trace) reports inside the outer trace.  Each event is credited with its
own seconds only — its duration less that of the events nested in it —
so the four ``*_s`` counters add up to the time spent compiling with no
second counted twice.  Each event's own seconds are also credited to the
innermost open span (``compile/span_s:<span>``), and, with the tracer on,
recorded as a ``compile`` instant on the ``compile`` track.
"""
from __future__ import annotations

import threading
import time
from typing import Optional

import jax

from repro import obs

_local = threading.local()


def _stack() -> list:
    s = getattr(_local, "spans", None)
    if s is None:
        s = _local.spans = []
    return s


def current_span() -> Optional[str]:
    """The innermost span open on this thread, or None."""
    s = getattr(_local, "spans", None)
    return s[-1] if s else None


class span:
    """``with obs.span("engine/prefill", tokens=n) as sp:`` — a host span
    (see the module docstring).  ``sp.t0``/``sp.t1`` are the
    ``time.monotonic()`` bounds and ``sp.seconds`` the duration, for call
    sites that also feed a timer or record per-slot residency from them.
    ``track``/``event`` override the tracer's names for the span."""

    __slots__ = ("name", "args", "track", "event", "t0", "t1", "_ann")

    def __init__(self, name: str, *, track: Optional[str] = None,
                 event: Optional[str] = None, **args):
        self.name = name
        self.args = args
        ns, _, rest = name.partition("/")
        self.track = track or ns
        self.event = event or rest or name

    def __enter__(self) -> "span":
        self._ann = jax.profiler.TraceAnnotation(self.name, **self.args)
        self._ann.__enter__()
        _stack().append(self.name)
        self.t0 = time.monotonic()
        return self

    def __exit__(self, *exc) -> bool:
        self.t1 = time.monotonic()
        _local.spans.pop()
        self._ann.__exit__(*exc)
        tracer = obs.get().tracer
        if tracer.enabled:
            tracer.complete(self.track, self.event, tracer.since(self.t0),
                            tracer.since(self.t1), **self.args)
        return False

    @property
    def seconds(self) -> float:
        return self.t1 - self.t0


def named_program(fn, name: str):
    """``fn`` under the fixed name ``name``: ``jax.jit`` of the result
    compiles a program (HLO module ``jit_<name>``) whose name no refactor
    of the Python function changes — the name a profile is read by."""
    def program(*args, **kwargs):
        return fn(*args, **kwargs)
    program.__name__ = program.__qualname__ = name
    program.__wrapped__ = fn        # jit reads the signature through it
    return program


# ------------------------------------------------------ compile counters
_DISPATCH_EVENTS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile/trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile/lower_s",
    "/jax/core/compile/backend_compile_duration": "compile/backend_s",
}
_CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


def _frames() -> list:
    f = getattr(_local, "compiles", None)
    if f is None:
        f = _local.compiles = []
    return f


def _on_start(event: str, _value, **_kw) -> None:
    # JAX reports each dispatch event's start as a scalar; the open frame
    # collects the seconds of the events that end inside it
    if event in _DISPATCH_EVENTS:
        _frames().append([event, 0.0])


def _on_duration(event: str, seconds: float, **kw) -> None:
    if event in _DISPATCH_EVENTS:
        frames = _frames()
        nested = 0.0
        if frames and frames[-1][0] == event:
            nested = frames.pop()[1]
        if frames:
            frames[-1][1] += seconds
        _credit(_DISPATCH_EVENTS[event], seconds - nested, kw)
        if event.endswith("backend_compile_duration"):
            obs.get().registry.counter("compile/programs").add()
    elif event == _CACHE_LOAD_EVENT:
        frames = _frames()
        if frames:              # the backend compile the load runs inside
            frames[-1][1] += seconds
        _credit("compile/cache_load_s", seconds, kw)


def _credit(counter: str, own: float, kw: dict) -> None:
    o = obs.get()
    o.registry.counter(counter).add(own)
    where = current_span()
    if where is not None:
        o.registry.counter("compile/span_s", label=where).add(own)
    if o.tracing:
        o.tracer.instant("compile", "compile", kind=counter,
                         fun_name=kw.get("fun_name", "?"),
                         span=where or "", seconds=own)


# once per process: a module is imported once
jax.monitoring.register_scalar_listener(_on_start)
jax.monitoring.register_event_duration_secs_listener(_on_duration)

"""Unified observability layer: typed metrics + trajectory span tracing.

One process-wide :class:`Observability` bundle holds the active
:class:`~repro.obs.metrics.MetricsRegistry` and span tracer.  Call sites
fetch it once per scope via :func:`get` — the default is metrics **on**
(they feed ``last_stats`` and the jsonl training log, which existing
tests assert on) and tracing **off** (a :class:`NullTracer`).

Enable tracing either programmatically::

    from repro import obs
    obs.configure(trace=True, trace_dir="results/trace")

or from the environment before launch::

    REPRO_TRACE_DIR=results/trace python examples/train_tool_agent.py

Tests use :func:`scoped` to swap in an isolated bundle for one block.

Host work is timed with :func:`span` (``obs/spans.py``): a
``jax.profiler.TraceAnnotation`` that any profiler session records on the
device trace's clock, and a complete event of the tracer when it is on.
Importing this package registers the process's compile listener, which
keeps the ``compile/*`` counters.  ``REPRO_JAX_PROFILE=<dir>``
additionally wraps the first traced scheduler rounds in ``jax.profiler``
(handled in core/scheduler.py, not here).
"""
from __future__ import annotations

import contextlib
import os
from dataclasses import dataclass

from .metrics import (Counter, Gauge, Histogram, MetricsRegistry,
                      NULL_REGISTRY, Timer, TIME_BUCKETS, VALUE_BUCKETS)
from .trace import NULL_TRACER, NullTracer, SpanTracer, validate_chrome_trace

__all__ = [
    "Counter", "Gauge", "Histogram", "Timer", "MetricsRegistry",
    "NULL_REGISTRY", "TIME_BUCKETS", "VALUE_BUCKETS",
    "SpanTracer", "NullTracer", "NULL_TRACER", "validate_chrome_trace",
    "Observability", "get", "configure", "scoped",
    "span", "current_span", "named_program",
]


@dataclass
class Observability:
    registry: MetricsRegistry
    tracer: object  # SpanTracer | NullTracer

    @property
    def tracing(self) -> bool:
        return bool(getattr(self.tracer, "enabled", False))


def _default() -> Observability:
    trace_dir = os.environ.get("REPRO_TRACE_DIR")
    tracer = (SpanTracer(out_dir=trace_dir) if trace_dir else NULL_TRACER)
    return Observability(registry=MetricsRegistry(enabled=True),
                         tracer=tracer)


_current: Observability = _default()


def get() -> Observability:
    """The active process-wide observability bundle."""
    return _current


def configure(metrics: bool = True, trace: bool = False,
              trace_dir: str = os.path.join("results", "trace"),
              max_events: int = 65536) -> Observability:
    """Replace the process-wide bundle.  Returns the new bundle."""
    global _current
    tracer = (SpanTracer(max_events=max_events, out_dir=trace_dir)
              if trace else NULL_TRACER)
    _current = Observability(
        registry=MetricsRegistry(enabled=metrics) if metrics
        else NULL_REGISTRY,
        tracer=tracer)
    return _current


@contextlib.contextmanager
def scoped(metrics: bool = True, trace: bool = False,
           trace_dir: str = os.path.join("results", "trace"),
           max_events: int = 65536):
    """Context manager swapping in an isolated bundle (test isolation)."""
    global _current
    prev = _current
    bundle = configure(metrics=metrics, trace=trace, trace_dir=trace_dir,
                       max_events=max_events)
    try:
        yield bundle
    finally:
        _current = prev


# imported last: spans.py reads ``get`` from this module at call time, and
# registers the process's compile listener as it is imported
from .spans import current_span, named_program, span  # noqa: E402

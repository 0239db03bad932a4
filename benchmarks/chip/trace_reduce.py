"""From a profiler trace (``.xplane.pb``, read with
``jax.profiler.ProfileData``) to the numbers the metric readers use.

* The window is the ``bench.window`` host span the benchmark opens and
  closes around the measured window, on the trace's own clock.
* A device's busy time is the union of the intervals of its op events
  (the ``XLA Ops`` line of each ``/device:TPU:N`` plane), clipped to the
  window; ``busy_s`` averages it over the chips used.
* Each device op's self time (its duration less that of the ops nested in
  it, as a while loop holds its body) is summed by its HLO instruction
  name, and each program's time (``XLA Modules`` line) by its name.
* The program's own host spans (``obs.span``) are the host events with a
  slash-namespaced name, ``<namespace>/<step>`` (``engine/alloc``,
  ``rollout/round``), whatever the namespace.  ``span_s`` sums each
  name's seconds open inside the window, clipped to it; a span nested in
  another counts under its own name as well as inside the outer one.
* An idle gap is a stretch of the window with no op on the device; it is
  named by the innermost host span open at its middle, the benchmark's
  (``engine.extend_rows``) or the program's (``engine/alloc``), or
  ``host`` where none is.
"""
from __future__ import annotations

import dataclasses
import glob
import heapq
import re
import warnings
from pathlib import Path
from typing import Dict, List, Tuple

HOST_SPANS = ("engine.generate", "engine.extend_rows", "engine.reset_rows",
              "engine.start", "tools.drain", "learner.make_batch",
              "learner.step")
# the program's span names; a profiler's Python-tracer events start "$"
PROGRAM_SPAN = re.compile(r"[a-z][a-z0-9_]*/[^\s#$]+")


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float
    op_s: Dict[str, float]            # op name -> seconds (all chips)
    module_s: Dict[str, float]        # program name -> seconds (all chips)
    gaps: List[Tuple[str, float]]     # (host span, seconds), longest first
    n_devices: int
    span_s: Dict[str, float]          # program span -> seconds in window

    def op_time(self, *needles: str) -> float:
        return sum(v for k, v in self.op_s.items()
                   if any(n in k for n in needles))

    def module_time(self, *needles: str) -> float:
        return sum(v for k, v in self.module_s.items()
                   if any(n in k for n in needles))


def xplane_file(trace_dir) -> str:
    found = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def op_name(event_name: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``%fusion.3``."""
    return event_name.split(" = ", 1)[0]


def _self_times(events):
    """(name, start, end) sorted by start -> {name: self seconds}; an op
    that holds later ops (a loop and its body) loses their time."""
    out: Dict[str, float] = {}
    stack = []                       # [name, end, self_ns]
    for name, s, e in events:
        while stack and (stack[-1][1] <= s or stack[-1][1] < e):
            n, _, own = stack.pop()
            out[n] = out.get(n, 0.0) + own * 1e-9
        if stack:
            stack[-1][2] -= (e - s)
        stack.append([name, e, e - s])
    for n, _, own in stack:
        out[n] = out.get(n, 0.0) + own * 1e-9
    return out


def _union(intervals):
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def summarize(path: str, n_devices: int = 1) -> Summary:
    from jax.profiler import ProfileData
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        return _summarize(ProfileData.from_file(path), n_devices)


def host_spans(pd) -> tuple:
    """The ``bench.window`` span's (start, end) and every benchmark or
    program host span as (start, end, name), in ns on the trace's clock."""
    spans, window = [], None
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == "bench.window":
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif e.name in HOST_SPANS or PROGRAM_SPAN.fullmatch(e.name):
                    spans.append((e.start_ns, e.start_ns + e.duration_ns,
                                  e.name))
    if window is None:
        raise ValueError("the trace holds no bench.window span")
    return window, spans


def span_seconds(spans, w0: float, w1: float) -> Dict[str, float]:
    """Program span name -> its seconds open inside [w0, w1] (ns)."""
    out: Dict[str, float] = {}
    for s, e, name in spans:
        t = min(e, w1) - max(s, w0)
        if t > 0 and PROGRAM_SPAN.fullmatch(name):
            out[name] = out.get(name, 0.0) + t * 1e-9
    return out


def innermost(spans, points) -> List[str]:
    """For each of ``points`` (ascending, ns) the name of the shortest span
    open at it (the first listed among equals), or ``host``."""
    order = sorted(range(len(spans)), key=lambda i: spans[i][0])
    heap, out, k = [], [], 0
    for p in points:
        while k < len(order) and spans[order[k]][0] <= p:
            i = order[k]
            heapq.heappush(heap, (spans[i][1] - spans[i][0], i))
            k += 1
        while heap and spans[heap[0][1]][1] < p:   # closed: for good,
            heapq.heappop(heap)                    # as points ascend
        out.append(spans[heap[0][1]][2] if heap else "host")
    return out


def _summarize(pd, n_devices: int) -> Summary:
    window, host = host_spans(pd)
    devices = [p for p in pd.planes if p.name.startswith("/device:TPU:")
               and p.name[len("/device:TPU:"):].isdigit()]
    w0, w1 = window
    devices = sorted(devices, key=lambda p: int(p.name.split(":")[-1]))
    devices = devices[:n_devices]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    op_s: Dict[str, float] = {}
    module_s: Dict[str, float] = {}
    busy_total = 0.0
    first_busy = None
    for plane in devices:
        ops = []
        for line in plane.lines:
            is_ops = line.name == "XLA Ops"
            is_mod = line.name == "XLA Modules"
            if not (is_ops or is_mod):
                continue
            for e in line.events:
                s = max(e.start_ns, w0)
                t = min(e.start_ns + e.duration_ns, w1)
                if t <= s:
                    continue
                if is_ops:
                    ops.append((op_name(e.name), s, t))
                else:
                    module_s[e.name] = (module_s.get(e.name, 0.0)
                                        + (t - s) * 1e-9)
        ops.sort(key=lambda o: (o[1], -o[2]))
        for name, sec in _self_times(ops).items():
            op_s[name] = op_s.get(name, 0.0) + sec
        merged = _union([(s, t) for _, s, t in ops])
        busy_total += sum(t - s for s, t in merged) * 1e-9
        if first_busy is None:
            first_busy = merged
    gaps = []                        # (middle, seconds), in time order
    cursor = w0
    for s, t in (first_busy or []) + [[w1, w1]]:
        if s > cursor:
            gaps.append(((cursor + s) / 2, (s - cursor) * 1e-9))
        cursor = max(cursor, t)
    names = innermost(host, [m for m, _ in gaps])
    gaps = sorted(zip(names, (sec for _, sec in gaps)), key=lambda g: -g[1])
    return Summary(window_s=(w1 - w0) * 1e-9, busy_s=busy_total / len(devices),
                   op_s=op_s, module_s=module_s, gaps=gaps,
                   n_devices=len(devices), span_s=span_seconds(host, w0, w1))


def breakdown(s: Summary, n: int = 10) -> dict:
    """The device ops that took most time and the longest idle gaps by
    the innermost host span open in each, at most ``n`` of each."""
    ops = sorted(s.op_s.items(), key=lambda kv: -kv[1])[:n]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in s.gaps[:n]]}

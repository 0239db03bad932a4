"""Span tracer — per-trajectory lifecycle timelines exported as Chrome
trace-event JSON (load ``results/trace/*.trace.json`` in Perfetto or
``chrome://tracing``).

The model is deliberately tiny: a :class:`SpanTracer` holds a bounded ring
buffer of trace events.  Call sites record **complete spans** (phase
``"X"``: a named interval on a named track, e.g. ``slot3: decode_round``)
and **instant events** (phase ``"i"``: e.g. ``weight_refresh`` at a round
boundary, ``cow`` on a copy-on-write barrier).  Tracks map to Chrome
``tid``s; ``export()`` prepends metadata events naming each track so the
viewer shows "slot 0", "slot 1", ... "tools", "learner" as separate rows.

Timestamps come from one shared ``time.monotonic()`` epoch per tracer, so
spans recorded from the scheduler thread and the background tool loop
line up on the same timeline.  Everything is microseconds (the Chrome
format's unit) and clamped non-negative.  The export's ``otherData``
carries the epoch's wall-clock nanoseconds (``epoch_unix_ns``), the clock
a ``jax.profiler`` trace keeps: an event at ``ts`` happened at
``epoch_unix_ns + 1000 * ts``, and in a profile whose ``Task Environment``
plane reads ``profile_start_time`` its xplane ``start_ns`` is that less
``profile_start_time`` — one offset maps the export onto the device trace.

:class:`NullTracer` is the disabled twin: every method is a no-op,
``now()`` is a constant, ``export()`` writes nothing.  Call sites branch
on ``tracer.enabled`` only to skip *argument construction*, never for
correctness.
"""
from __future__ import annotations

import collections
import json
import os
import threading
import time
from typing import Deque, Dict, List, Optional

_VALID_PHASES = ("X", "i", "M")


class SpanTracer:
    """Bounded-buffer trace recorder with Chrome trace-event export."""

    enabled = True

    def __init__(self, max_events: int = 65536,
                 out_dir: Optional[str] = None, pid: int = 0):
        self.max_events = int(max_events)
        self.out_dir = out_dir
        self.pid = pid
        self._epoch = time.monotonic()
        self.epoch_unix_ns = time.time_ns()
        self._events: Deque[dict] = collections.deque(maxlen=self.max_events)
        self._tracks: Dict[str, int] = {}
        self._lock = threading.Lock()
        self._n_exports = 0

    # ------------------------------------------------------------ recording
    def now(self) -> float:
        """Seconds since this tracer's epoch (pass to ``complete``)."""
        return time.monotonic() - self._epoch

    def since(self, t_monotonic: float) -> float:
        """A ``time.monotonic()`` reading in this tracer's epoch seconds."""
        return t_monotonic - self._epoch

    def track_id(self, track: str) -> int:
        tid = self._tracks.get(track)
        if tid is None:
            with self._lock:
                tid = self._tracks.setdefault(track, len(self._tracks))
        return tid

    def complete(self, track: str, name: str, t0: float, t1: float,
                 **args) -> None:
        """Record a complete span [t0, t1] (epoch-relative seconds) on
        ``track``."""
        ts = max(0.0, t0) * 1e6
        dur = max(0.0, t1 - t0) * 1e6
        ev = {"ph": "X", "name": name, "pid": self.pid,
              "tid": self.track_id(track), "ts": ts, "dur": dur}
        if args:
            ev["args"] = args
        self._events.append(ev)

    def instant(self, track: str, name: str, t: Optional[float] = None,
                **args) -> None:
        """Record an instant event (vertical tick) on ``track``."""
        ts = max(0.0, self.now() if t is None else t) * 1e6
        ev = {"ph": "i", "name": name, "pid": self.pid,
              "tid": self.track_id(track), "ts": ts, "s": "t"}
        if args:
            ev["args"] = args
        self._events.append(ev)

    # ------------------------------------------------------------ export
    def events(self) -> List[dict]:
        """Current buffer contents with track-name metadata prepended."""
        meta = [{"ph": "M", "name": "thread_name", "pid": self.pid,
                 "tid": tid, "ts": 0,
                 "args": {"name": track}}
                for track, tid in sorted(self._tracks.items(),
                                         key=lambda kv: kv[1])]
        return meta + list(self._events)

    def export(self, label: str = "rollout") -> str:
        """Write the buffer as Chrome trace JSON and clear it.  Returns the
        file path ("" if there is no out_dir or nothing was recorded)."""
        if self.out_dir is None or not self._events:
            return ""
        obj = {"traceEvents": self.events(),
               "displayTimeUnit": "ms",
               "otherData": {"clock": "monotonic",
                             "epoch_unix_ns": self.epoch_unix_ns}}
        os.makedirs(self.out_dir, exist_ok=True)
        with self._lock:
            self._n_exports += 1
            n = self._n_exports
        path = os.path.join(self.out_dir,
                            f"{label}_{n:04d}.trace.json")
        with open(path, "w") as f:
            json.dump(obj, f)
        self._events.clear()
        return path


class NullTracer:
    """Disabled tracer: every operation is a no-op costing one attribute
    lookup and a call."""

    enabled = False
    epoch_unix_ns = 0
    __slots__ = ()

    def now(self) -> float:
        return 0.0

    def since(self, t_monotonic: float) -> float:
        return 0.0

    def track_id(self, track: str) -> int:
        return 0

    def complete(self, track, name, t0, t1, **args) -> None:
        pass

    def instant(self, track, name, t=None, **args) -> None:
        pass

    def events(self) -> List[dict]:
        return []

    def export(self, label: str = "rollout") -> str:
        return ""


NULL_TRACER = NullTracer()


# --------------------------------------------------------------- validation
def validate_chrome_trace(obj) -> List[str]:
    """Schema-check a parsed Chrome trace object.  Returns a list of
    human-readable problems (empty = valid).  Used by tests and the
    scripts/check.sh trace smoke."""
    errs: List[str] = []
    if not isinstance(obj, dict) or "traceEvents" not in obj:
        return ["top level must be an object with a 'traceEvents' list"]
    events = obj["traceEvents"]
    if not isinstance(events, list):
        return ["'traceEvents' must be a list"]
    named_tids = set()
    for ev in events:
        if ev.get("ph") == "M" and ev.get("name") == "thread_name":
            named_tids.add(ev.get("tid"))
    for i, ev in enumerate(events):
        ph = ev.get("ph")
        if ph not in _VALID_PHASES:
            errs.append(f"event {i}: unknown phase {ph!r}")
            continue
        if not isinstance(ev.get("name"), str) or not ev["name"]:
            errs.append(f"event {i}: missing name")
        ts = ev.get("ts")
        if not isinstance(ts, (int, float)) or ts < 0:
            errs.append(f"event {i} ({ev.get('name')}): bad ts {ts!r}")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                errs.append(f"event {i} ({ev.get('name')}): bad dur {dur!r}")
        if ph in ("X", "i"):
            tid = ev.get("tid")
            if tid not in named_tids:
                errs.append(f"event {i} ({ev.get('name')}): tid {tid!r} "
                            "has no thread_name metadata")
    return errs

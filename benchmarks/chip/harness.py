"""What every cell shares: finding the cell and its files by name (the
configuration's layout and reference modules among them), the compile
cache, the chip check, compile counting, the traced window, the metric
readers and the result line.

The command's checkout root holds ``BENCHMARK.json``; everything the
benchmark reads lies under this directory, found by the names there.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
import math
import os
import sys
import time
from pathlib import Path
from typing import Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
CONFIGS = HERE / "configs"
CACHE_DIR = ROOT / ".jax_cache"
OUT_DIR = HERE / "out"


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list
    per_layer: list
    limits: dict


def load_cell(name: str) -> Cell:
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    cfg = json.loads((ROOT / conf["file"]).read_text())
    for key in ("layout", "reference"):
        if not (CONFIGS / f"{cfg.get(key)}.py").is_file():
            raise SystemExit(f"configuration {conf['name']!r} names no "
                             f"{key} module {cfg.get(key)!r} in {CONFIGS}")
        config_module(cfg, key)
    traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                         .read_text())
    e2e = [m for m in bench["end_to_end"] if name in m.get("workloads",
                                                           [name])]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if name in m.get("workloads", [name])
                 and m["moves"] in e2e_names]
    lim_path = HERE / "limits" / f"{name}.json"
    limits = json.loads(lim_path.read_text()) if lim_path.exists() else {}
    return Cell(name, int(w["chips"]), cfg, traffic, e2e, per_layer, limits)


def config_module(cfg: dict, key: str):
    """The module ``configs/<cfg[key]>.py`` that a configuration names:
    ``layout``, all that the harness knows of the model family, or
    ``reference``, the plain reference."""
    return _load(CONFIGS / f"{cfg[key]}.py")


def layout(cfg: dict):
    return config_module(cfg, "layout")


@functools.lru_cache(maxsize=None)
def _load(path: Path):
    spec = importlib.util.spec_from_file_location(path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def enable_cache() -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, for every program however short its compile."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(CACHE_DIR)


def require_chips(n: int) -> dict:
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no accelerator: JAX runs on {devs[0].platform}")
    if len(devs) < n:
        raise NoChip(f"{len(devs)} chips, the cell asks for {n}")
    return device_info(n)


def device_info(n: int) -> dict:
    import jax
    devs = jax.devices()[:n]
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def memory_peak_bytes(n: int) -> int:
    import jax
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in jax.devices()[:n]]
    return int(max(peaks))


class CompileCount:
    """Counts JAX's compile events from a listener registered once per
    process: programs compiled or loaded (``backend_compile``), of those
    the loads from the persistent cache (``cache_hit``), and jaxpr traces;
    ``names`` keeps the programs compiled or loaded, in order."""
    _counts = {"backend_compile": 0, "cache_hit": 0, "trace": 0}
    names: list = []
    _registered = False

    def __init__(self):
        import jax.monitoring
        if not CompileCount._registered:
            jax.monitoring.register_event_duration_secs_listener(self._on)
            CompileCount._registered = True

    @staticmethod
    def _on(name, _dur, **kw):
        if name.endswith("backend_compile_duration"):
            CompileCount._counts["backend_compile"] += 1
            CompileCount.names.append(kw.get("fun_name", "?"))
        elif name.endswith("cache_retrieval_time_sec"):
            CompileCount._counts["cache_hit"] += 1
        elif name.endswith("jaxpr_trace_duration"):
            CompileCount._counts["trace"] += 1

    def snapshot(self) -> dict:
        return dict(CompileCount._counts, n_names=len(CompileCount.names))

    @staticmethod
    def since(before: dict) -> dict:
        now = CompileCount._counts
        out = {k: now[k] - before[k] for k in now}
        out["compiled"] = out["backend_compile"] - out["cache_hit"]
        out["names"] = sorted(set(CompileCount.names[before["n_names"]:]))
        return out


class Span:
    """A host span on the profiler's clock, named like the layer it wraps;
    also sums its own host-clock seconds."""

    def __init__(self, name: str, totals: Optional[dict] = None):
        import jax
        self._ann = jax.profiler.TraceAnnotation(name)
        self.name, self.totals = name, totals

    def __enter__(self):
        self._t0 = time.perf_counter()
        self._ann.__enter__()
        return self

    def __exit__(self, *exc):
        self._ann.__exit__(*exc)
        if self.totals is not None:
            self.totals[self.name] = (self.totals.get(self.name, 0.0)
                                      + time.perf_counter() - self._t0)
        return False


class TracedWindow:
    """The profiler around the measured window.  ``open`` starts the
    profiler (outside the window) and then the ``bench.window`` span that
    marks the window on the trace's clock; ``close`` ends both."""

    def __init__(self, enabled: bool, cell: str, seed: int):
        self.enabled = enabled
        self.dir = OUT_DIR / f"{cell}.{seed}"
        self._ann = None

    def open(self) -> None:
        if not self.enabled:
            return
        import jax
        jax.profiler.start_trace(str(self.dir))
        self._ann = jax.profiler.TraceAnnotation("bench.window")
        self._ann.__enter__()

    def close(self) -> None:
        if self._ann is None:
            return
        import jax
        self._ann.__exit__(None, None, None)
        self._ann = None
        jax.profiler.stop_trace()


@dataclasses.dataclass
class Record:
    """What one run measured, for the metric readers.  ``counts`` holds
    the driver's counters over the window, and the program's registry
    (``obs.get().registry.snapshot()``) as it stood at the window's opening
    and close, ``program_open`` and ``program_close``; ``trace`` the
    reduced profile (traced runs only)."""
    cell: str
    config: dict
    traffic: dict
    device: dict
    peak: dict
    setup_s: float
    window_s: float
    counts: dict
    trace: Optional[object] = None
    memory_peak_bytes: int = 0


def program_delta(rec: Record, key: str) -> float:
    """How far the program's registry value ``key`` (a counter, or a
    timer's ``/count`` or ``/sum``) moved in the window: at the close less
    at the opening, 0 for a key absent."""
    c = rec.counts
    return (c.get("program_close", {}).get(key, 0.0)
            - c.get("program_open", {}).get(key, 0.0))


def read_metrics(metrics: list, rec: Record) -> dict:
    """Run each metric's reader (``metrics/<name>.py``); a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in metrics:
        path = HERE / "metrics" / f"{m['name']}.py"
        spec = importlib.util.spec_from_file_location(
            "metric_" + m["name"].replace(".", "_"), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        v = mod.read(rec)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def judge(numbers: dict, limits: dict) -> tuple:
    """Each number compared with its limit (a number passes at or under
    it).  A number with no limit, or one that is not finite, fails."""
    checks, ok = {}, True
    for name, value in numbers.items():
        lim = limits.get(name, {}).get("limit")
        checks[name] = {"value": value, "limit": lim}
        if lim is None or not math.isfinite(value) or value > lim:
            ok = False
    return checks, ok


def emit(result: dict) -> None:
    """The compared numbers as the last lines on standard error, and the
    result as the last line on standard output, ``checks`` last."""
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']!r} limit {c['limit']!r}",
              file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)

"""Tiny cells for the CPU tests: the benchmark's own configurations and
mixes with small widths, depth and traffic, run through the same drivers."""
from __future__ import annotations

import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))

import harness as H  # noqa: E402


def tiny_cell(workload: str, config_over=None, **traffic_over) -> H.Cell:
    """The cell ``<config>.<mix>`` built from its files (whether or not
    ``BENCHMARK.json`` lists it yet), cut to the tiny widths its layout
    names (``TINY``)."""
    conf, mix_name = workload.split(".", 1)
    cfg = json.loads((HERE / "configs" / f"{conf}.json").read_text())
    cfg = dict(cfg, **H.layout(cfg).TINY, **(config_over or {}))
    mix = json.loads((HERE / "traffic" / f"{mix_name}.json").read_text())
    if mix["entry"] == "rollout":
        mix.update(slots=4, max_len=512, pool_blocks=96, group_size=2,
                   tasks=150, max_new_tokens=16, check_trajectories=3,
                   question_bytes=[8, 24], warm_freed_blocks=40)
        if mix["max_tool_calls"]:
            mix.update(observation_tokens=[24, 48],
                       tool_latency={"median_s": 0.01, "sigma": 0.5,
                                     "timeout_s": 5.0})
        else:
            mix.update(max_new_tokens=32)
    else:
        mix.update(batches=4, length=[40, 64], max_seq_len=64,
                   prompt_tokens=[8, 16], model_span=[4, 8],
                   observation_span=[4, 8], group_size=4)
    mix.update(traffic_over)
    limits = json.loads((HERE / "limits" / "tiny.json").read_text())
    return H.Cell(workload, 1, cfg, mix, [], [], limits)


def run_tiny(workload: str, seed: int, seconds: float = 2.0, fault=None,
             config_over=None, **traffic_over) -> dict:
    """One run of the tiny cell through ``run.execute``, past the chip
    check; returns the result line."""
    import contextlib
    import io
    import json as _json

    import run as R
    cell = tiny_cell(workload, config_over, **traffic_over)
    buf = io.StringIO()
    kw = {} if fault is None else {"fault": fault}
    with contextlib.redirect_stdout(buf):
        R.execute(cell, seed, seconds, False, H.device_info(1), **kw)
    return _json.loads(buf.getvalue().strip().splitlines()[-1])

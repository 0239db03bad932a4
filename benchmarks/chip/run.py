"""Run one benchmark cell once on the chips of this machine.

    python benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell, its configuration, traffic mix, metrics and limits are found by
name from ``BENCHMARK.json`` at the checkout root.  With ``--trace 0`` the
result carries the cell's end-to-end metrics, with ``--trace 1`` its
per-layer metrics, read from a profiler trace of the window.  The last
line of standard output is one JSON object; the numbers compared with the
plain reference, each beside its limit, close standard error and the
result line.  Exits non-zero, printing no result, without a TPU or with
fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness as H  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    cell = H.load_cell(args.workload)
    try:
        device = H.require_chips(cell.chips)
    except H.NoChip as e:
        print(f"refused: {e}", file=sys.stderr)
        return 2
    H.enable_cache()
    return execute(cell, args.seed, args.seconds, bool(args.trace), device)


def execute(cell, seed, seconds, traced, device, **driver_kw) -> int:
    import peaks
    driver = __import__({"rollout": "rollout", "learner": "learner"}[
        cell.traffic["entry"]])
    out = driver.run(cell, seed, seconds, traced, T_START, **driver_kw)
    summary = None
    if traced:
        import trace_reduce
        t0 = time.perf_counter()
        summary = trace_reduce.summarize(
            trace_reduce.xplane_file(out["trace_dir"]), cell.chips)
        shutil.rmtree(out["trace_dir"], ignore_errors=True)
        top = sorted(summary.module_s.items(), key=lambda kv: -kv[1])[:8]
        spans = sorted(summary.span_s.items(), key=lambda kv: -kv[1])[:12]
        H.log(f"trace read in {time.perf_counter() - t0:.1f} s; programs "
              f"by device seconds: {top}; program spans by host seconds: "
              f"{spans}")
    # off the chip (the CPU tests) no device metric is written
    on_chip = device["platform"] == "tpu"
    rec = H.Record(cell=cell.name, config=cell.config, traffic=cell.traffic,
                   device=device,
                   peak=peaks.peaks(device["kind"]) if on_chip else {},
                   setup_s=out["setup_s"], window_s=out["window_s"],
                   counts=out["counts"], trace=summary,
                   memory_peak_bytes=out["memory_peak_bytes"])
    metrics = H.read_metrics(cell.per_layer if traced else cell.end_to_end,
                             rec) if on_chip else {}
    checks, ok = H.judge(out["numbers"], cell.limits)
    device = dict(device, memory_peak_bytes=out["memory_peak_bytes"])
    result = {"correct": ok,
              "attempted": out["attempted"], "failed": out["failed"],
              "metrics": metrics, "device": device}
    if summary is not None:
        import trace_reduce
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s
        result["breakdown"] = trace_reduce.breakdown(summary)
    result["checks"] = checks
    H.emit(result)
    return 0


if __name__ == "__main__":
    sys.exit(main())

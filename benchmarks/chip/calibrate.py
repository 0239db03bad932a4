"""Readings that set each compared number's limit, on the chip at the
cell's own size.  Not run by the benchmark's runs.

    python benchmarks/chip/calibrate.py --workload <cell> --seeds 1,2,3 \\
        --seconds 51 [--int8-kv]

For each seed, one run of the cell (a window at the cell's own load, then
the check) prints the numbers compared and whether they pass the cell's
limits.  Every seed also reads the control, put in the program's place
and judged against the same limits: for a rollout cell the plain
reference with every product's inputs in fp8, for a learner cell the
reference in bfloat16 and the half-batch fault.  ``--int8-kv`` (rollout
cells) runs the program with its own int8 K/V pool switched on, the
program's path one precision below the served bf16, so that its numbers
are judged too.  One JSON line per seed goes to standard output and to
``out/calibrate.<cell>.jsonl``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

import harness as H  # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--int8-kv", action="store_true")
    args = ap.parse_args()
    cell = H.load_cell(args.workload)
    H.require_chips(cell.chips)
    H.enable_cache()
    H.OUT_DIR.mkdir(parents=True, exist_ok=True)
    out_path = H.OUT_DIR / f"calibrate.{cell.name}.jsonl"
    entry = cell.traffic["entry"]
    driver = __import__(entry)
    reuse: dict = {}
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        if entry == "rollout":
            out = driver.run(cell, seed, args.seconds, False, t0,
                             kv_dtype="int8" if args.int8_kv else "fp",
                             reuse=reuse, control=True)
        else:
            out = driver.run(cell, seed, args.seconds, False, t0,
                             variants=True)
        _, ok = H.judge(out["numbers"], cell.limits)
        line = {"cell": cell.name, "seed": seed,
                "variant": "int8_kv" if args.int8_kv else "program",
                "numbers": out["numbers"], "correct": ok,
                "controls": {name: {"numbers": nums,
                                    "correct": H.judge(nums, cell.limits)[1]}
                             for name, nums in out["extra"].items()},
                "setup_s": out["setup_s"], "window_s": out["window_s"],
                "attempted": out["attempted"], "failed": out["failed"],
                "tokens": out["counts"].get("tokens"),
                "memory_peak_bytes": out["memory_peak_bytes"],
                "wall_s": time.perf_counter() - t0}
        print(json.dumps(line), flush=True)
        with open(out_path, "a") as f:
            f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())

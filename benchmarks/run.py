"""Benchmark harness — one entry per paper table/figure plus the roofline
table.  Prints ``name,us_per_call,derived`` CSV lines (and richer per-bench
output above them).

``--list`` imports and prints every registered bench without running any —
the quick-tier smoke that the registry resolves (scripts/check.sh).
``--only LABEL`` runs a single bench by its registry label.
"""
from __future__ import annotations

import argparse
import importlib
import sys
import time

# label -> module under benchmarks/ (declaration order is run order)
REGISTRY = (
    ("table1_async_throughput", "bench_async_throughput"),
    ("continuous_rollout", "bench_continuous_rollout"),
    ("async_refresh", "bench_async_refresh"),
    ("decode_throughput", "bench_decode_throughput"),
    ("paged_cache", "bench_paged_cache"),
    ("prefix_sharing", "bench_prefix_sharing"),
    ("decode_roofline", "bench_decode_roofline"),
    ("kernels", "bench_kernels"),
    ("obs_overhead", "bench_obs_overhead"),
    ("fig5_training_curve", "bench_training_curve"),
    ("roofline", "roofline"),
)


def _resolve(modname: str):
    return importlib.import_module(f"benchmarks.{modname}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--list", action="store_true",
                    help="import + list every registered bench, run none")
    ap.add_argument("--only", metavar="LABEL",
                    help="run a single bench by registry label")
    args = ap.parse_args(argv)

    if args.list:
        for label, modname in REGISTRY:
            mod = _resolve(modname)       # import failure = broken registry
            assert callable(getattr(mod, "main", None)), modname
            print(f"{label:28s} benchmarks/{modname}.py")
        return 0

    selected = REGISTRY
    if args.only:
        selected = [e for e in REGISTRY if e[0] == args.only]
        if not selected:
            known = ", ".join(label for label, _ in REGISTRY)
            print(f"unknown bench {args.only!r}; known: {known}",
                  file=sys.stderr)
            return 2

    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    all_rows = []
    failed = []
    for label, modname in selected:
        print(f"===== {label} =====", flush=True)
        t0 = time.monotonic()
        try:
            rows = _resolve(modname).main() or []
        # one failing bench must not hide the others' rows; the run still
        # exits non-zero below
        except Exception as e:  # lint: disable=broad-except
            print(f"{label},ERROR,{type(e).__name__}: {e}")
            failed.append(label)
            rows = []
        all_rows.extend(rows)
        print(f"({label} took {time.monotonic()-t0:.0f}s)", flush=True)

    print("\nname,us_per_call,derived")
    for name, us, derived in all_rows:
        print(f"{name},{us:.1f},{derived}")
    if failed:
        print(f"FAILED: {', '.join(failed)}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())

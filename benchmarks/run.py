"""Benchmark harness entry: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only name]

Emits ``benchmark,case,metric,value`` CSV rows (also saved under
benchmarks/results/) — see EXPERIMENTS.md for the paper-claim mapping.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback


SUITES = ["alpha", "locality", "comm_volume", "end_to_end", "ablation",
          "merging", "sensitivity", "accuracy", "roofline", "planning",
          "cache", "features", "resilience", "obs", "serve", "membership"]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true",
                    help="paper-scale datasets (slow on 1 CPU core)")
    ap.add_argument("--only", default=None)
    args = ap.parse_args()
    from repro.compile_cache import use_compile_cache
    use_compile_cache()

    suites = [args.only] if args.only else SUITES
    failures = []
    for name in suites:
        mod = __import__(f"benchmarks.{name}", fromlist=["run"])
        print(f"\n===== {name} =====")
        t0 = time.perf_counter()
        try:
            mod.run(quick=not args.full)
            print(f"----- {name} done in {time.perf_counter() - t0:.1f}s")
        except Exception:                               # noqa: BLE001
            failures.append(name)
            traceback.print_exc()
    if failures:
        print(f"\nFAILED suites: {failures}")
        sys.exit(1)
    print("\nall benchmark suites completed")


if __name__ == "__main__":
    main()

"""Benchmark harness: one module per paper table/figure + roofline.

  PYTHONPATH=src python -m benchmarks.run            # all, CI-scaled
  PYTHONPATH=src python -m benchmarks.run --only accuracy runtime
  PYTHONPATH=src python -m benchmarks.run --scale 1.0  # paper-size rows

Outputs one JSON per benchmark under results/bench/ and a summary CSV of
``name,pass,seconds`` to stdout.  The roofline benchmark reads the dry-run
artifacts (results/dryrun) and is skipped when absent.
"""
from __future__ import annotations

import argparse
import json
import os
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", nargs="*", default=None)
    ap.add_argument("--scale", type=float, default=0.05,
                    help="study row-count scale (1.0 = paper size)")
    ap.add_argument("--out", default="results/bench")
    args = ap.parse_args()
    os.makedirs(args.out, exist_ok=True)

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from . import (accuracy, convergence, runtime, scalability, roofline,
                   secure_overhead)

    benches = {
        "accuracy": lambda: accuracy.run(scale=args.scale),
        "convergence": lambda: convergence.run(scale=args.scale),
        "runtime": lambda: runtime.run(scale=args.scale),
        "scalability": lambda: scalability.run(
            records_each=max(200, int(10_000 * args.scale))
        ),
        "secure_overhead": lambda: secure_overhead.run(
            sizes=(10_000, 100_000, 1_000_000)
        ),
        "roofline": lambda: roofline.run(),
    }
    if args.only:
        benches = {k: v for k, v in benches.items() if k in args.only}

    print("name,pass,seconds,rows")
    failures = 0
    for name, fn in benches.items():
        t0 = time.perf_counter()
        try:
            rows = fn()
        except Exception as e:  # pragma: no cover
            print(f"{name},ERROR({type(e).__name__}: {e}),"
                  f"{time.perf_counter() - t0:.2f},0")
            failures += 1
            continue
        dt = time.perf_counter() - t0
        with open(os.path.join(args.out, f"{name}.json"), "w") as f:
            json.dump(rows, f, indent=2)
        ok = all(r.get("pass", True) for r in rows if isinstance(r, dict))
        failures += 0 if ok else 1
        print(f"{name},{ok},{dt:.2f},{len(rows)}")
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()

"""Benchmark suite entry point: ``python -m benchmarks.run [--quick]``.

One benchmark per paper table:
  * gemm_detection  — Table II (simulated-error detection accuracy, GEMM)
  * eb_detection    — Table III (simulated-error detection accuracy, EB)

Chip timings come from the chip benchmark (``BENCHMARK.json``).
"""
from __future__ import annotations

import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="subset of shapes / smaller tables")
    ap.add_argument("--only", default=None,
                    help="run a single benchmark by name")
    args = ap.parse_args()

    from benchmarks import eb_detection, gemm_detection

    benches = {
        "gemm_detection": gemm_detection.main,
        "eb_detection": eb_detection.main,
    }
    if args.only:
        benches = {args.only: benches[args.only]}
    for name, fn in benches.items():
        print(f"\n=== {name} ===", flush=True)
        t0 = time.time()
        fn(quick=args.quick)
        print(f"=== {name} done in {time.time()-t0:.1f}s ===", flush=True)


if __name__ == "__main__":
    main()

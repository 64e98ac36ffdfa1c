#!/usr/bin/env python3
"""Run one benchmark cell once on the accelerator this machine holds.

    python3 benchmarks/chip/run.py --workload dlrm-table1.pool100 \
        --seed 7 --seconds 20 --trace 0

Run from the root of a checkout.  The cell, its configuration, traffic
and metrics are named in ``BENCHMARK.json``.  The last line of standard
output is one JSON object (``correct``, ``attempted``, ``failed``,
``metrics``, ``device``, with ``--trace 1`` also ``breakdown``, then the
numbers compared under ``checks``); the checks are also the last lines of
standard error.  Without an accelerator, or with fewer chips than the
cell asks for, it exits non-zero and prints no result.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
CHECKOUT = os.path.dirname(os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")

    # libtpu would otherwise write its logs to a fixed path under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, os.path.join(CHECKOUT, "src"))
    sys.path.insert(0, HERE)
    import harness

    try:
        result = harness.run(args.workload, args.seed, args.seconds,
                             bool(args.trace), t0=T0)
    except harness.NoDevice as e:
        print(f"run.py: {e}", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Read the numbers that set a cell's limits, on the chip.

    python3 benchmarks/chip/calibrate.py --workload dlrm-table1.pool100 \
        --seeds 101,102,... --control-seeds 101,102,103 --seconds 2 \
        --out calibrate.json

In one process, for each seed: one run of the cell with a short window
(the program's reading of every number compared) and, for the control
seeds, the control's reading on the same sample and its verdict against
the cell's limits: the reference at int4, the precision below the
configuration's int8, in the program's place.
With ``--fault`` the program runs with that fault of ``faults.py``
planted.  Writes every reading to ``--out`` and prints a summary.
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
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--control-bits", type=int, default=4)
    ap.add_argument("--fault", default=None,
                    help="plant this fault of faults.py in the timed path")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(CHECKOUT, "src"))
    sys.path.insert(0, HERE)
    import contextlib

    import faults
    import harness

    control = {int(s) for s in args.control_seeds.split(",") if s}
    rows = []
    for s in (int(s) for s in args.seeds.split(",")):
        t = time.perf_counter()
        with (faults.plant(args.fault) if args.fault
              else contextlib.nullcontext()):
            res = harness.run(args.workload, s, args.seconds, False,
                              control_bits=args.control_bits if s in control
                              else None)
        row = {"seed": s, "fault": args.fault, "correct": res["correct"],
               "attempted": res["attempted"],
               "checks": {k: v["value"] for k, v in res["checks"].items()},
               "control": res.get("control"),
               "seconds": time.perf_counter() - t}
        rows.append(row)
        print(json.dumps(row), flush=True)
    os.makedirs(os.path.dirname(os.path.abspath(args.out)), exist_ok=True)
    with open(args.out, "w") as f:
        json.dump({"workload": args.workload, "rows": rows}, f, indent=1)
    gaps = [r["checks"]["logit_gap"] for r in rows]
    ctl = [r["control"] for r in rows if r["control"]]
    print(f"program logit_gap: max {max(gaps):.6g} over {len(gaps)} seeds, "
          f"correct on {sum(r['correct'] for r in rows)}; control: "
          f"min {min((c['logit_gap'] for c in ctl), default=float('nan')):.6g}"
          f" over {len(ctl)} seeds, correct on "
          f"{sum(c['correct'] for c in ctl)}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Readings of the correctness comparison on many seeds in one process:
the program's, or its control's (``--control``), where the program
breaks one guarantee the configuration states.

  python3 bench/control.py --workload join.1node --seconds 1 \
      --seeds 11,12,13 [--control]

Each seed is a whole run of the cell (set-up, a window of ``--seconds``,
the comparison) through ``harness.run``; one JSON line per seed gives
the numbers compared.  The benchmark's own runs never run the control;
this is how its readings, which set the limits, are taken on the chip.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# libtpu would otherwise keep its logs under a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)
    for path in (ROOT, os.path.join(ROOT, "src")):
        if path not in sys.path:
            sys.path.insert(0, path)
    from bench import harness
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        out = harness.run(args.workload, seed, args.seconds, False,
                          t_start=t0, control=args.control)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": args.control,
                          "correct": out["correct"],
                          "attempted": out["attempted"],
                          "checks": out["checks"],
                          "metrics": out["metrics"],
                          "seconds": time.perf_counter() - t0}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

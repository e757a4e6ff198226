#!/usr/bin/env python
"""Read the numbers a cell's check compares, for the program and for the
control, on many seeds in one process (one process holds the chip).

    python benchmark/control.py --workload <cell> --seeds 11 12 13 \\
        --seconds 5 [--program] [--control] [--traffic <mix>] [--rehearse]

--program runs the cell as the benchmark does; --control puts the
driver's control in the program's place: the plain reference computed so
that it breaks one guarantee the configuration states, or the program
with such a path of its own switched on.  --traffic runs another mix of the cell's driver.  One JSON line
per run: the seed, which side, `correct` and the checks.  The limits in
the drivers were set from these readings (PERF.md).  The benchmark's own
runs never run the control.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--program", action="store_true")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--traffic")
    ap.add_argument("--rehearse", action="store_true")
    args = ap.parse_args(argv)
    sides = [s for s in ("program", "control") if getattr(args, s)]
    if not sides:
        ap.error("give --program, --control or both")
    for seed in args.seeds:
        for side in sides:
            try:
                out = run.run_cell(args.workload, seed, args.seconds, False,
                                   rehearse=args.rehearse,
                                   control=side == "control",
                                   traffic=args.traffic)
            except run.NoChip as e:
                run.log(f"FAIL: {e}")
                return 3
            print(json.dumps({"seed": seed, "side": side,
                              "traffic": args.traffic,
                              "correct": out["correct"],
                              "attempted": out["attempted"],
                              "metrics": out["metrics"],
                              "checks": out["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

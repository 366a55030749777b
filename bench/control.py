#!/usr/bin/env python3
"""Run a cell's control on the chip: the plain reference of the
deployment's family in the program's place, computed in bfloat16 (one step
below the float32 the configuration states), compared as a run's output
is. Every seed must come out not correct; the numbers it reads are the
upper readings the limits sit under.

    python3 bench/control.py --workload <name> --seeds 1,2,3

Each seed is a full set-up and a one-second window at the cell's size; the
check then replaces the program's state after the checked batch, and its
stats rows, with the control's.
"""
import argparse
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    bad = 0
    for seed in [int(s) for s in args.seeds.split(",")]:
        out = run.run_cell(args.workload, seed, 1.0, False,
                           t_start=time.time(), control=True)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": out["correct"],
                          "checks": out["checks"]}), flush=True)
        bad += out["correct"]
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main())

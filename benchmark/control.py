"""Read the program and the lower-precision control side by side, on
several seeds in one process, at the cell's own size:

    python benchmark/control.py --workload <cell> --seconds <s> --seeds 1 2 3

For each number the comparison reads it prints the largest the program
gave and the smallest the control gave: a limit holds only between the
two, with room on both sides (PERF.md gives the readings each limit was
set from). The control is the reference computed with 8-bit float
operands in every matmul (benchmark/reference.py, `lowp`), put in the
program's place. The benchmark's own runs never run it.
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args()
    from benchmark import run

    program, control = {}, {}
    for seed in args.seeds:
        result = run.run_cell(args.workload, seed, args.seconds, 0,
                              control=True, t_process=time.perf_counter())
        print(json.dumps({"seed": seed, "checks": result["checks"],
                          "control": result["control"]}), flush=True)
        for name, value in result["checks"].items():
            program.setdefault(name, []).append(value)
        for name, value in result["control"].items():
            control.setdefault(name, []).append(value)
    for name in sorted(set(control) & set(program)):
        print("%-30s program's largest %-22r control's smallest %-22r ratio %.1f"
              % (name, max(program[name]), min(control[name]),
                 min(control[name]) / max(max(program[name]), 1e-30)))


if __name__ == "__main__":
    main()

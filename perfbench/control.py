"""The control: the reference, one precision lower, in the program's place.

    python3 perfbench/control.py --workload <cell> --seconds <s> --seeds 1 2 3

Runs the cell as `run.py` does, once per seed in one process, but
compares the control's answers instead of the program's: a stable sort
of each compared input by its keys' top 24 bits, computed on the chip
(`harness.reference.control_sort`). Every result line it prints must
read `"correct": false`; the smallest `mismatched_keys` among them is
the comparison's upper reading. The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import sys

import run
from harness import reference


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    for seed in args.seeds:
        rc = run.main(["--workload", args.workload, "--seed", str(seed),
                       "--seconds", str(args.seconds), "--trace", "0"],
                      produce=reference.control_sort)
        if rc:
            return rc
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Readings of the control: the reference in bfloat16 in the program's place.

    python bench/control.py --workload <cell> --seeds <s1,s2,...>

For each seed it draws one call of the cell at the cell's own size, takes
the same sample a run's check takes, and prints the numbers that
``check.py`` compares when the bfloat16 reference stands in for the
program.  The smallest of them, over the seeds, is each limit's upper
reading (PERF.md).  The benchmark's own runs never run it.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench import check  # noqa: E402
from bench import harness as H  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    res = H.resolve(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        numbers = check.compare([(seed, {}, None)], res["config"],
                                res["traffic"], seed, control="bfloat16")
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control": numbers,
                          "correct": check.verdict(numbers)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

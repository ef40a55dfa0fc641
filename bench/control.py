"""Readings that set a cell's limit: the program's check number over many
seeds, and the control's on the same tokens, in one process.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed it makes a whole run of the cell (``harness.run_cell``)
with the control in the program's place in the check: the plain
reference computed with float8 matrix products, one precision below the
configuration's bfloat16, read at each position of the same prompts and
served tokens.  Each line gives the program's own reading
(``program_gap``), the control's (``control_gap``) and ``correct``,
which the control has to make false.  The benchmark's own runs never
read the control.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args(argv)
    from bench import harness

    for seed in [int(s) for s in args.seeds.split(",")]:
        r = harness.run_cell(args.workload, seed, args.seconds, False,
                             control=True)
        print(json.dumps({"seed": seed, "correct": r["correct"],
                          "program_gap": r["program_served_gap"],
                          "control_gap": r["checked"]["served_gap"]["value"],
                          "attempted": r["attempted"],
                          "metrics": {k: v["value"]
                                      for k, v in r["metrics"].items()}}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

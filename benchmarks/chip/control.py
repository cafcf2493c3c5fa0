"""Readings that a cell's correctness limits are set from.

  python benchmarks/chip/control.py --workload <cell> --seeds 1,2,3 \\
      [--cpu-rehearsal]

For each seed, in one process, at the cell's own sizes: the numbers the
cell compares for the program (its timed path, against the plain
reference), for the control (the reference in the precision below the
configuration's) and, for a training cell, for faults planted in the
reference put in the program's place.  One JSON line per seed, then a
summary: the program's largest reading of each number (the lower
reading), the control's smallest, each fault's smallest, and the
cell's limit.  The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys

import run_cell


def summarize(rows, limits):
    def lowest(key):
        return {k: min(r[key][k] for r in rows) for k in limits}

    out = {"program_max": {k: max(r["program"][k] for r in rows)
                           for k in limits},
           "control_min": lowest("control"), "limits": limits}
    faults = rows[0].get("faults", {})
    for name in faults:
        out[f"{name}_min"] = {k: min(r["faults"][name][k] for r in rows)
                              for k in limits}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated whole numbers")
    ap.add_argument("--cpu-rehearsal", action="store_true")
    args = ap.parse_args(argv)
    seeds = [int(s) for s in args.seeds.split(",")]
    try:
        cell = run_cell.prepare(args.workload, seeds[0], 0.0, False,
                                args.cpu_rehearsal)
    except run_cell.CellError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    rows = cell.kind.readings(cell, seeds)
    for row in rows:
        print(json.dumps(row), flush=True)
    print(json.dumps(summarize(rows, cell.limits)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

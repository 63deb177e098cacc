#!/usr/bin/env python3
"""Reads the control of `correct` at the cell's own size: the plain
reference put in the program's place and computed in float32 (money as
float32 dollars, sums and averages in float32): the nearest precision below
the doubles the configuration states, and a break of its guarantee that
decimal answers are exact.  Compared with the reference by the comparison a
run uses, for every parameter set of the cell's traffic.  Host work only.

    python3 benchmarks/selfcheck/control.py --workload tpcds-sf1.q07 --seeds 1,2,3
"""

import argparse
import json
import os
import shutil
import sys
import tempfile

import numpy as np

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmarks.harness import cells, compare   # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="the config's rehearse_rows, not its rows")
    args = ap.parse_args()
    import auron_tpu  # noqa: F401  (the generator builds the program's schemas)
    from benchmarks.harness import datagen
    cell = cells.load_cell(args.workload)
    rows = cell.config["rehearse_rows" if args.rehearse else "rows"]
    for seed in (int(s) for s in args.seeds.split(",")):
        work = tempfile.mkdtemp(prefix="auron-bench-control-")
        try:
            cat = datagen.generate(work, cell.query.SCANS, rows,
                                   cell.config["data_seed"], seed)
            readings = []
            for params in cell.traffic["param_sets"]:
                want = cell.query.reference(cat.read, params)
                control = cell.query.reference(cat.read, params, np.float32)
                readings.append(compare.compare_tables(control, want))
        finally:
            shutil.rmtree(work, ignore_errors=True)
        # the control has to fail every parameter set: the least reading
        least = {k: min(r[k] for r in readings) for k in readings[0]}
        verdict = compare.judge(least, cell.query.LIMITS)
        print(json.dumps({"workload": cell.name, "seed": seed,
                          "rows": rows["store_sales"],
                          "control_correct": verdict["ok"],
                          "checks": verdict["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

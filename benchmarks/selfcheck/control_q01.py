#!/usr/bin/env python3
"""Reads the controls of `correct` for query 1 at the cell's own size: the
plain reference put in the program's place and computed (a) with its sums
and its average in float32 — the nearest precision below what the
configuration states, which has to break `rows_differ` — and (b) with the
sums exact and the average, the product and the comparison in float64,
reported beside it for what it is.  Compared with the reference by the
comparison a run uses, for every parameter set of the cell's traffic.
Host work only.

    python3 benchmarks/selfcheck/control_q01.py --seeds 1,2,3 [--rehearse]
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

CELL = "tpcds-sf10.q01"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="the config's rehearse_rows, not its rows")
    args = ap.parse_args()
    import auron_tpu  # noqa: F401  (the generator builds the program's schemas)
    from benchmarks.harness import datagen
    cell = cells.load_cell(CELL)
    rows = cell.config["rehearse_rows" if args.rehearse else "rows"]
    for seed in (int(s) for s in args.seeds.split(",")):
        work = tempfile.mkdtemp(prefix="auron-bench-control-")
        try:
            cat = datagen.generate(work, cell.query.SCANS, rows,
                                   cell.config["data_seed"], seed)
            out = {"workload": cell.name, "seed": seed,
                   "rows": rows["store_returns"]}
            for params in cell.traffic["param_sets"]:
                want = cell.query.reference(cat.read, params)
                for name, kw in (("float32", {"dtype": np.float32}),
                                 ("float64_average",
                                  {"avg_dtype": np.float64})):
                    reading = compare.compare_tables(
                        cell.query.reference(cat.read, params, **kw), want)
                    out.setdefault(name, []).append({
                        "select": params["SELECT"],
                        "control_correct": compare.judge(
                            reading, cell.query.LIMITS)["ok"],
                        **reading})
        finally:
            shutil.rmtree(work, ignore_errors=True)
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""Reads `correct`'s numbers over many seeds in one process, at the cell's
own size, where a run per seed would spend most of its time in set-up: for
each seed the tables are written anew over the same files (so the plans,
and with them the compiled programs, stay), the query is executed once
having to read them (the fresh-scan cells' path) and once more from the
program's caches (the cached cells' path), and both answers are compared
with the plain reference as a run compares them; the float32 control is
read on the same tables.  One line of JSON per seed.

    python3 benchmarks/selfcheck/seeds.py --workload tpcds-sf1.q07 --seeds 1,2,3
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np

sys.path[0] = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

from benchmarks import run                      # noqa: E402
from benchmarks.harness import cells, compare   # noqa: E402


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    cell = cells.load_cell(args.workload)
    run.find_devices(cell.chips, args.rehearse_cpu)
    import auron_tpu  # noqa: F401
    from benchmarks.harness import datagen
    from benchmarks.harness.compile_log import CompileLog
    rows = cell.config["rehearse_rows" if args.rehearse_cpu else "rows"]
    compile_log = CompileLog()
    session = run.new_session()
    work = tempfile.mkdtemp(prefix="auron-bench-seeds-")
    try:
        for seed in (int(s) for s in args.seeds.split(",")):
            cat = datagen.generate(work, cell.query.SCANS, rows,
                                   cell.config["data_seed"], seed)
            out = {"workload": cell.name, "seed": seed}
            for k, params in enumerate(cell.traffic["param_sets"]):
                plan = cell.query.build_plan(cat, params)
                want = cell.query.reference(cat.read, params)
                for path in ("fresh", "cached"):
                    before = compile_log.snapshot()["programs"]
                    t0 = time.perf_counter()
                    got = session.execute(plan).table
                    out[f"{path}_{k}"] = dict(
                        compare.compare_tables(got, want),
                        wall_s=time.perf_counter() - t0,
                        programs=compile_log.snapshot()["programs"] - before)
                out[f"control_{k}"] = compare.compare_tables(
                    cell.query.reference(cat.read, params, np.float32), want)
            print(json.dumps(out), flush=True)
    finally:
        shutil.rmtree(work, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

#!/usr/bin/env python3
"""The program's spans of a process's FIRST executes of a benchmark cell.

The harness (`benchmarks/run.py`) warms up with `auron.trace.enable` off and
observes the window alone, so what an execute costs before the process is
warm (PERF.md section 7: `tpcds-sf10.q07`'s `query_s.p95` is the window's
first execute) is read here: the cell's first plan, `--executes` times, with
tracing on from the first, one line an execute — its wall, its `host_syncs`,
the milliseconds under each span name, the args of the result's three crossings and of the
sort, the self time of `spmd.gather` and `task.execute`, and
inside `spmd.to_arrow` the milliseconds of each `column_to_arrow` call by
column type (timed from outside, in this script).  On the chip:

    chiprun -- python3 tools/first_executes.py --workload tpcds-sf10.q07 \\
        --seed 2147480123 --executes 7

writes `chiprun_out/first_executes.<workload>.json`; `--rehearse-cpu` runs
the cell's `rehearse_rows` on the CPU.
"""

import argparse
import json
import os
import sys
import tempfile
import time

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, _ROOT)


def self_ms(spans, name):
    """Milliseconds inside spans of one name less their children's."""
    ids = {s.id for s in spans if s.name == name}
    total = sum(s.dur_ns for s in spans if s.name == name)
    return (total - sum(s.dur_ns for s in spans if s.parent in ids)) / 1e6


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--executes", type=int, default=7)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()

    from benchmarks import run as bench
    from benchmarks.harness import cells, datagen
    cell = cells.load_cell(args.workload)
    devs = bench.find_devices(cell.chips, args.rehearse_cpu)
    import auron_tpu  # noqa: F401  (enables x64)
    from auron_tpu import config
    from auron_tpu.columnar import arrow_interop
    from auron_tpu.runtime import tracing

    mesh = None
    if cell.config["mesh_devices"] > 1:
        from auron_tpu.parallel.mesh import data_mesh
        mesh = data_mesh(cell.config["mesh_devices"])
    rows = cell.config["rehearse_rows" if args.rehearse_cpu else "rows"]

    # `column_to_arrow` timed from outside, by column type
    columns = []
    inner = arrow_interop.column_to_arrow

    def timed(dtype, col, n):
        t0 = time.perf_counter_ns()
        out = inner(dtype, col, n)
        columns.append((str(dtype), (time.perf_counter_ns() - t0) / 1e6))
        return out

    arrow_interop.column_to_arrow = timed
    lines = []
    with tempfile.TemporaryDirectory(prefix="auron-first-") as work_dir:
        cat = datagen.generate(os.path.join(work_dir, "data"),
                               cell.query.SCANS, rows,
                               cell.config["data_seed"], args.seed)
        plan = cell.query.build_plan(cat, cell.traffic["param_sets"][0])
        session = bench.new_session()
        with config.conf.scoped({"auron.trace.enable": True}):
            for n in range(1, args.executes + 1):
                del columns[:]
                t0 = time.perf_counter()
                res = session.execute(plan, mesh=mesh)
                wall = time.perf_counter() - t0
                spans = [s for s in res.trace.snapshot() if s.dur_ns >= 0]
                by_name = {}
                for s in spans:
                    by_name[s.name] = by_name.get(s.name, 0.0) \
                        + s.dur_ns / 1e6
                to_arrow = [s.args for s in spans
                            if s.name == "spmd.to_arrow"]
                crossings = {k: [s.args for s in spans if s.name == k]
                             for k in ("spmd.fetch", "ffi.to_device",
                                       "sort.run", "task.to_host")}
                record = tracing.find_query(res.query_id)
                line = {
                    "execute": n, "wall_s": wall, "span_ms": by_name,
                    "host_syncs": record.metric_totals.get("host_syncs"),
                    "task_syncs": [s.args.get("syncs") for s in spans
                                   if s.name == "task.execute"],
                    "self_ms": {k: self_ms(spans, k)
                                for k in ("spmd.gather", "task.execute",
                                          "spmd.tail", "spmd.launch")},
                    "to_arrow_args": to_arrow, "args": crossings,
                    # the gather's columns come first (an attempt the
                    # ladder discards converts none), the tail's after
                    "column_to_arrow_ms": columns[:sum(
                        a["columns"] for a in to_arrow)]}
                lines.append(line)
                print(json.dumps(line), flush=True)
    out = {"workload": cell.name, "seed": args.seed,
           "device": {"platform": devs[0].platform,
                      "kind": devs[0].device_kind},
           "executes": lines}
    os.makedirs(os.path.join(_ROOT, "chiprun_out"), exist_ok=True)
    path = os.path.join(_ROOT, "chiprun_out",
                        f"first_executes.{cell.name}.json")
    with open(path, "w") as f:
        json.dump(out, f)
    print(f"first_executes: wrote {path}", file=sys.stderr)


if __name__ == "__main__":
    main()

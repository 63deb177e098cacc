"""CLI: python -m auron_tpu.it --sf 0.01 --data-dir /tmp/tpcds
[--queries q03,q42] [--golden-dir tests/golden_plans] [--json out.json]

The `dev/auron-it` Main.scala:26 analogue."""

from __future__ import annotations

import argparse
import sys


def main() -> int:
    ap = argparse.ArgumentParser(prog="auron_tpu.it")
    ap.add_argument("--data-dir", default="/tmp/auron_tpcds")
    ap.add_argument("--sf", type=float, default=0.01)
    ap.add_argument("--queries", default=None,
                    help="comma-separated subset (default: all)")
    ap.add_argument("--golden-dir", default=None)
    ap.add_argument("--json", default=None, help="write results JSON here")
    ap.add_argument("--platform", default="cpu",
                    help="jax platform to run on (default cpu: the IT "
                         "differential suite is a correctness/CPU-gate "
                         "harness; pass 'tpu' to drive the device)")
    ap.add_argument("--mesh", type=int, default=0,
                    help="run compilable plans as ONE shard_map stage "
                         "program over an N-device mesh (N=1 compiles "
                         "the whole pipeline for a single chip; serial "
                         "fallback stays transparent)")
    ap.add_argument("--perf-factor", type=float, default=0.0,
                    help="arm the perf gate: warm native (best of two "
                         "post-compile runs, recorded as native_warm_s) "
                         "must stay within FACTOR x the oracle; 0 = "
                         "cold-only (no warm runs)")
    ap.add_argument("--analyze", action="store_true",
                    help="print EXPLAIN ANALYZE per query (merged "
                         "per-task metric trees rendered against the "
                         "executed plan; serial path shows per-operator "
                         "rows/batches/compute)")
    ap.add_argument("--trace-dir", default=None,
                    help="record a query-lifecycle trace per query "
                         "(auron.trace.enable) and write Chrome-trace "
                         "JSON files <dir>/<query>.trace.json")
    ap.add_argument("--stage-compare", action="store_true",
                    help="instead of the differential run, execute every "
                         "query through BOTH the serial walk and the "
                         "1-device stage compiler and record warm times "
                         "per query (the IT_STAGE.json generator)")
    args = ap.parse_args()

    if args.platform:
        # --platform chooses the backend explicitly, whatever
        # JAX_PLATFORMS says; the env var is exported too, for any
        # worker subprocesses
        import os

        import jax
        os.environ["JAX_PLATFORMS"] = args.platform
        jax.config.update("jax_platforms", args.platform)
        # session-level persistent-compile-cache default
        # (auron.compile.cache.dir: device backends only under 'auto')
        from auron_tpu.config import apply_compile_cache
        apply_compile_cache()

    from auron_tpu.it.datagen import generate
    from auron_tpu.it.runner import QueryRunner

    print(f"generating sf={args.sf} data into {args.data_dir} ...",
          flush=True)
    cat = generate(args.data_dir, sf=args.sf)

    if args.stage_compare:
        if args.mesh or args.golden_dir:
            ap.error("--stage-compare is a 1-device serial-vs-stage "
                     "comparison; --mesh/--golden-dir do not apply")
        return _stage_compare(cat, args)

    runner = QueryRunner(catalog=cat, golden_dir=args.golden_dir)
    if args.perf_factor:
        runner.perf_factor = args.perf_factor
    if args.mesh:
        from auron_tpu.parallel.mesh import data_mesh
        runner.mesh = data_mesh(args.mesh)
    runner.analyze = args.analyze
    if args.trace_dir:
        from auron_tpu.config import conf as _conf
        _conf.set("auron.trace.enable", True)
        runner.trace_dir = args.trace_dir
    names = args.queries.split(",") if args.queries else None
    # per-query incremental flush: a crash (an sf10 run OOMed at query
    # ~90 of 103 and lost 2h of results) or a driver kill still leaves
    # every completed query's record on disk.  Atomic tmp+rename: a kill
    # mid-write must not truncate the records already saved.
    import json as _json
    import os as _os

    def flush(r):
        line = {k: v for k, v in r.to_dict().items() if v is not None}
        print(_json.dumps(line), flush=True)
        if args.json:
            tmp = args.json + ".tmp"
            with open(tmp, "w") as f:
                f.write(runner.to_json())
            _os.replace(tmp, args.json)

    runner.run_all(names, on_result=flush)
    print(runner.report())
    return 0 if all(r.ok for r in runner.results) else 1


def _stage_compare(cat, args) -> int:
    """Per-query serial vs 1-device-stage warm comparison (IT_STAGE.json
    generator): each query runs cold + warm through the serial per-batch
    walk, then cold + warm with auron.spmd.singleDevice.enable."""
    import json
    import time

    import jax

    from auron_tpu import conf
    from auron_tpu.frontend.session import AuronSession
    from auron_tpu.it import queries
    from auron_tpu.it.oracle import PyArrowEngine

    names = args.queries.split(",") if args.queries else queries.names()
    rows = []
    for name in names:
        rec = {"name": name}
        try:
            plan = queries.build(name, cat)
            counts = {}
            for mode, flag in (("serial", False), ("stage", True)):
                with conf.scoped(
                        {"auron.spmd.singleDevice.enable": flag}):
                    s = AuronSession(foreign_engine=PyArrowEngine())
                    s.execute(plan)
                    t0 = time.perf_counter()
                    r1 = s.execute(plan)
                rec[f"{mode}_warm_s"] = round(time.perf_counter() - t0, 4)
                if mode == "stage":
                    rec["spmd"] = bool(r1.spmd)
                counts[mode] = r1.table.num_rows
            rec["rows"] = counts["serial"]
            if counts["stage"] != counts["serial"]:
                rec["error"] = (f"row-count divergence: serial "
                                f"{counts['serial']} vs stage "
                                f"{counts['stage']}")
        except Exception as e:  # noqa: BLE001 — per-query isolation
            rec["error"] = str(e)[:120]
        rows.append(rec)
        print(json.dumps(rec), flush=True)
        # accumulated CPU executables segfault this jaxlib eventually
        # (see tests/test_tpcds_it.py runner note)
        jax.clear_caches()
    if args.json:
        with open(args.json, "w") as f:
            json.dump(rows, f, indent=1)
    staged = [r for r in rows if r.get("spmd")]
    sp = sorted(r["serial_warm_s"] / r["stage_warm_s"] for r in staged
                if r.get("stage_warm_s"))
    if sp:
        print(f"# staged {len(staged)}/{len(rows)}; warm speedup "
              f"median {sp[len(sp) // 2]:.2f}x")
    return 0 if all("error" not in r for r in rows) else 1


if __name__ == "__main__":
    sys.exit(main())

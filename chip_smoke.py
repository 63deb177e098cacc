#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that auron_tpu still starts on a TPU.

One process drives the main path the way a user does
(`AuronSession.execute` -> convert -> SPMD stage compiler -> Arrow out, what
`auron_tpu.it.runner.QueryRunner.run` does) under the default configuration,
on TPC-DS q01, q07 and q19 over the repo's generator at `--sf` (default 3:
3,000,000 `store_sales` rows, the cardinality of dsdgen SF1), and holds every
result to the pyarrow oracle on the host.  Then q01 once more on the serial
per-batch engine, where guard trips and rejected plans land in production.

There is no CPU branch: the script exits non-zero, and prints no result,
unless `jax.devices()[0].platform == "tpu"`.  One process per chip — nothing
here starts a child that needs the device.

    python chip_smoke.py                 # one chip; what the driver runs
    python chip_smoke.py --chips 4       # the four-chip phase and ONLY that
    python chip_smoke.py --sf 0.01 --queries q01     # canary

The one-chip run fits the 1200 s it is given (about 920 s from an empty
compile cache).  The four-chip phase compiles four to six mesh programs and
at the default sf needs more than that: see PERF.md before starting one.

Every line of standard output is one JSON object, flushed as produced; they
are observations of this one run, not metrics.  Times measured around host
work are labelled host.  The last line is
`{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}`.
The compile cache is wherever `auron_tpu.config.apply_compile_cache` puts it
(`JAX_COMPILATION_CACHE_DIR` when set, else `<repo>/.jax_cache`); the script
sets none of its own.
"""

from __future__ import annotations

import argparse
import importlib.metadata
import json
import os
import sys
import tempfile
import time

QUERIES = ("q01", "q07", "q19")
MESH_QUERIES = ("q01", "q07")      # the --chips 4 phase
# the store_sales projection q07 scans: its device-resident copy is the
# HBM-residency evidence
Q07_FACT_COLUMNS = ("ss_sold_date_sk", "ss_item_sk", "ss_promo_sk",
                    "ss_quantity", "ss_sales_price")

_FAILURES: list = []


def emit(obj: dict) -> None:
    print(json.dumps(obj), flush=True)


def failed(msg: str) -> None:
    """Record a failed check and go on to the next observation: the run
    still exits non-zero and never prints the ok line."""
    _FAILURES.append(msg)
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)


class CompileLog:
    """Counts JAX's own trace / compile / persistent-cache events, so the
    numbers hold whether or not jitcheck (off by default) is armed."""

    def __init__(self):
        import jax.monitoring as mon
        self.n = {"traces": 0, "programs": 0, "cache_hits": 0,
                  "cache_misses": 0}
        self.compile_s = 0.0
        mon.register_event_duration_secs_listener(self._on_duration)
        mon.register_event_listener(self._on_event)

    def _on_duration(self, event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.n["traces"] += 1
        elif event == "/jax/core/compile/backend_compile_duration":
            # one per program handed to the backend: compiled, or loaded
            # from the persistent cache
            self.n["programs"] += 1
            self.compile_s += secs

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.n["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.n["cache_misses"] += 1

    def snapshot(self) -> dict:
        return dict(self.n, compile_s=self.compile_s)

    @staticmethod
    def delta(before: dict, after: dict) -> dict:
        return {k: (round(after[k] - before[k], 3) if k == "compile_s"
                    else after[k] - before[k]) for k in after}


def new_session():
    """A fresh session, as QueryRunner.run makes one (imports wait until
    the device check has passed)."""
    from auron_tpu.frontend.session import AuronSession
    from auron_tpu.it.oracle import PyArrowEngine
    return AuronSession(foreign_engine=PyArrowEngine())


def timed_execute(session, plan, mesh=None):
    t0 = time.perf_counter()
    res = session.execute(plan, mesh=mesh)
    return res, time.perf_counter() - t0


def run_oracle(plan):
    """The pyarrow oracle on the host, as QueryRunner.run does it."""
    from auron_tpu import config
    with config.conf.scoped({"auron.enable": False}):
        return timed_execute(new_session(), plan)


def check_against_oracle(label: str, plan, res, oracle) -> bool:
    from auron_tpu.it import compare
    diff = compare.compare_tables(res.table, oracle.table,
                                  ordered=compare.plan_is_ordered(plan))
    if diff is not None:
        failed(f"{label}: result differs from the oracle: {diff}")
    return diff is None


def check_stage_result(label: str, res) -> dict:
    """The checks every stage-path execute must pass; returns what was
    observed."""
    from auron_tpu.runtime import tracing
    from auron_tpu.runtime.explain_analyze import metric_totals
    rec = tracing.find_query(res.query_id)
    fallbacks = int(metric_totals(res.metrics).get("num_fallbacks", 0)) + \
        int(rec.fallbacks if rec is not None else 0)
    if not res.all_native():
        failed(f"{label}: a foreign section ran on the host engine")
    if res.spmd is not True or res.spmd_rejection is not None:
        failed(f"{label}: not run by the stage compiler "
               f"(spmd={res.spmd}, rejection={res.spmd_rejection})")
    if fallbacks:
        failed(f"{label}: num_fallbacks={fallbacks}")
    return {"spmd": bool(res.spmd), "all_native": bool(res.all_native()),
            "fallbacks": fallbacks,
            # guard-ladder re-executions inside execute_plan_spmd: design,
            # not failure, but each one compiles another program
            "stage_retries": int(rec.retries if rec is not None else 0)}


def run_query(name: str, cat, log: CompileLog) -> None:
    """One cold and two warm executes on one session, the oracle, and
    every check the smoke holds the stage path to."""
    from auron_tpu.it import queries
    from auron_tpu.parallel import stage
    from auron_tpu.runtime import jitcheck

    plan = queries.build(name, cat)
    session = new_session()
    c0 = log.snapshot()
    res, cold_s = timed_execute(session, plan)
    c1 = log.snapshot()
    out = {"query": name, "cold_s": round(cold_s, 3),
           "rows": res.table.num_rows}
    out.update(check_stage_result(f"{name} cold", res))
    out["cold_compile"] = CompileLog.delta(c0, c1)

    sites0 = jitcheck.compile_counts()
    warm = []
    for i in range(2):
        wres, s = timed_execute(session, plan)
        warm.append(s)
        check_stage_result(f"{name} warm {i}", wres)
        if not wres.table.equals(res.table):
            failed(f"{name} warm {i}: result differs from the cold run")
    c2 = log.snapshot()
    out["warm_s"] = round(min(warm), 4)
    out["warm_compile"] = CompileLog.delta(c1, c2)
    grown = {k: v - sites0.get(k, 0)
             for k, v in jitcheck.compile_counts().items()
             if v != sites0.get(k, 0)}
    # jitcheck counts per site only when armed at process start
    # (AURON_TPU_AURON_JITCHECK_ENABLE); JAX's own events always count
    out["jitcheck_armed"] = jitcheck.enabled()
    if grown or out["warm_compile"]["traces"] or \
            out["warm_compile"]["programs"]:
        failed(f"{name}: warm executes compiled "
               f"(jitcheck sites {grown}, jax {out['warm_compile']})")
    out["gather"] = dict(stage.GATHER_STATS)

    oracle, oracle_s = run_oracle(plan)
    out["oracle_s_host"] = round(oracle_s, 3)
    out["equal_to_oracle"] = check_against_oracle(name, plan, res, oracle)
    emit(out)


def run_serial_fallback(cat) -> None:
    """q01 on the serial per-batch engine.  The one place this script
    scopes an engine option: production reaches this path through a guard
    trip or a rejected plan, which the smoke treats as failure above."""
    from auron_tpu import config
    from auron_tpu.it import queries
    plan = queries.build("q01", cat)
    with config.conf.scoped({"auron.spmd.singleDevice.enable": False}):
        res, s = timed_execute(new_session(), plan)
    if res.spmd:
        failed("q01 serial: ran on the stage compiler, not the serial "
               "engine")
    if not res.all_native():
        failed("q01 serial: a foreign section ran on the host engine")
    oracle, _ = run_oracle(plan)
    emit({"query": "q01", "engine": "serial", "cold_s": round(s, 3),
          "rows": res.table.num_rows,
          "equal_to_oracle": check_against_oracle("q01 serial", plan, res,
                                                  oracle)})


def fact_source_entries(n_devices: int):
    """Stage source-cache entries holding q07's store_sales projection,
    placed over `n_devices` devices (the stage module's own cache object,
    read the way its tests read it)."""
    import jax
    from auron_tpu.parallel import stage
    out = []
    for e in stage._DEVICE_SHARDS.values():
        if tuple(e["schema"].names()) != Q07_FACT_COLUMNS:
            continue
        leaves = jax.tree.leaves((e["cols"], e["live"]))
        if len(leaves[0].sharding.device_set) == n_devices:
            out.append(leaves)
    return out


def check_device_evidence(fact_rows: int) -> None:
    """Checked, not assumed: the fact columns lived in HBM on a TPU."""
    import jax
    entries = fact_source_entries(1)
    if not entries:
        failed("no store_sales source is device-resident after q07")
        return
    leaves = entries[0]
    platforms = sorted({d.platform for x in leaves for d in x.devices()})
    if platforms != ["tpu"]:
        failed(f"stage source arrays live on {platforms}, not on a TPU")
    # rows x the widths of the dtypes the engine holds them in (data,
    # validity and, for f64, the exact-bits sidecar)
    touched = fact_rows * sum(x.dtype.itemsize for x in leaves)
    stats = jax.devices()[0].memory_stats()
    peak = int(stats["peak_bytes_in_use"])
    emit({"peak_device_bytes": peak, "bytes_limit": stats.get("bytes_limit"),
          "q07_store_sales_device_bytes": touched,
          "q07_store_sales_device_dtypes": [str(x.dtype) for x in leaves],
          "source_platforms": platforms})
    if peak < touched:
        failed(f"peak device bytes {peak} < the {touched} bytes of the "
               f"store_sales columns q07 touches")


def run_mesh_phase(cat, log: CompileLog, fact_rows: int) -> None:
    """--chips 4: q01 and q07 over a 4-device mesh and over the 1-device
    mesh in this same process, both held to the oracle."""
    import jax
    from auron_tpu.it import queries
    from auron_tpu.parallel.mesh import data_mesh
    mesh4 = data_mesh(4)
    for name in MESH_QUERIES:
        plan = queries.build(name, cat)
        oracle, oracle_s = run_oracle(plan)
        out = {"query": name, "oracle_s_host": round(oracle_s, 3)}
        for label, mesh in (("mesh4", mesh4), ("mesh1", None)):
            session = new_session()
            c0 = log.snapshot()
            res, cold_s = timed_execute(session, plan, mesh=mesh)
            c1 = log.snapshot()
            _, warm_s = timed_execute(session, plan, mesh=mesh)
            obs = check_stage_result(f"{name} {label}", res)
            obs.update(cold_s=round(cold_s, 3), warm_s=round(warm_s, 4),
                       rows=res.table.num_rows,
                       cold_compile=CompileLog.delta(c0, c1),
                       equal_to_oracle=check_against_oracle(
                           f"{name} {label}", plan, res, oracle))
            out[label] = obs
        emit(out)
    entries = fact_source_entries(4)
    if not entries:
        failed("no store_sales source is sharded over 4 devices")
        return
    per_device: dict = {}
    for x in entries[0]:
        for s in x.addressable_shards:
            key = f"{s.device.platform}:{s.device.id}"
            per_device[key] = per_device.get(key, 0) + int(s.data.nbytes)
    emit({"store_sales_shard_devices": len(per_device),
          "store_sales_bytes_per_device": per_device,
          "store_sales_rows": fact_rows})
    if len(per_device) != 4:
        failed(f"store_sales shards sit on {len(per_device)} devices, "
               f"not 4")
    elif max(per_device.values()) > 1.5 * min(per_device.values()):
        failed(f"store_sales shards are uneven: {per_device}")
    emit({"peak_bytes_per_device": {
        f"{d.platform}:{d.id}": int(d.memory_stats()["peak_bytes_in_use"])
        for d in jax.devices()[:4]}})


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--sf", type=float, default=3.0,
                    help="generator scale: store_sales = 1,000,000 x sf "
                         "rows (default 3, dsdgen SF1's cardinality)")
    ap.add_argument("--seed", type=int, default=7)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs the four-chip phase and no other")
    ap.add_argument("--queries", default=",".join(QUERIES),
                    help="canary use only; the smoke is the default three")
    ap.add_argument("--out", default=os.path.join(
        tempfile.gettempdir(), "auron_chip_smoke"),
        help="where the generated data goes (never the checkout)")
    args = ap.parse_args()

    # device first: no accelerator, no run — and no CPU stand-in
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, JAX reports {devs[0].platform!r} "
            f"({devs[0].device_kind}); there is no CPU mode")
    if len(devs) < args.chips:
        raise SystemExit(f"chip_smoke: --chips {args.chips} needs "
                         f"{args.chips} devices, JAX reports {len(devs)}")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}

    import auron_tpu  # noqa: F401  (enables x64)
    from auron_tpu.it.datagen import generate
    from auron_tpu.native import bindings
    emit({"device": device, "chips_phase": args.chips,
          "versions": {p: importlib.metadata.version(p)
                       for p in ("jax", "jaxlib", "libtpu")}})
    log = CompileLog()

    import pyarrow.parquet as pq
    data_dir = os.path.join(args.out,
                            f"tpcds_sf{args.sf:g}_seed{args.seed}")
    t0 = time.perf_counter()
    cat = generate(data_dir, sf=args.sf, seed=args.seed)
    datagen_s = time.perf_counter() - t0
    rows = {n: sum(pq.read_metadata(p).num_rows for p in t.chunks)
            for n, t in cat.tables.items()}
    emit({"datagen_s_host": round(datagen_s, 2), "sf": args.sf,
          "seed": args.seed, "data_dir": data_dir,
          "rows": {k: rows[k] for k in ("store_sales", "store_returns",
                                        "customer", "item")}})

    if args.chips == 4:
        run_mesh_phase(cat, log, rows["store_sales"])
    else:
        names = [q for q in args.queries.split(",") if q]
        for name in names:
            run_query(name, cat, log)
        run_serial_fallback(cat)
        if "q07" in names:
            check_device_evidence(rows["store_sales"])

    cache_dir = jax.config.jax_compilation_cache_dir
    emit({"compile_cache_dir": cache_dir,
          "from_env": "JAX_COMPILATION_CACHE_DIR" in os.environ,
          "compile_cache_entries": sum(
              f.endswith("-cache") for f in os.listdir(cache_dir))
          if cache_dir and os.path.isdir(cache_dir) else 0,
          "totals": log.snapshot(),
          # the C++ host library builds itself on first use and quietly
          # gives way to the Python codecs when that fails: say which ran
          "native_host_library": bindings.available()})
    if _FAILURES:
        print(f"chip_smoke: {len(_FAILURES)} check(s) failed",
              file=sys.stderr, flush=True)
        return 1
    emit({"ok": True, "device": device})
    return 0


if __name__ == "__main__":
    sys.exit(main())

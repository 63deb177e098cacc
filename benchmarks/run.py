#!/usr/bin/env python3
"""Runs one cell of the benchmark once.

    python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The cell's files are found by the names in BENCHMARK.json (harness/cells.py).
The traffic file gives the query, its substitution parameters (one plan per
set, taken in an order drawn from the seed) and which tables' files are
replaced before every execute, so that the program has to read and ship them
anew.  Set-up (imports, data from the seed, the session, warm-up executes of
every plan until one compiles nothing) ends where the window starts; the
plain reference runs after the window and outside `setup_s`.  The last line
of standard output is the result; the numbers `correct` compares stand
beside their limits in its last key and on the last lines of standard error.

Without a TPU, or with fewer chips than the cell asks for, the command exits
non-zero before anything is measured.  `--rehearse-cpu` (never passed by the
driver) runs the same code at the config's `rehearse_rows` on the CPU and
prints no device metric.
"""

import time

T_START = time.perf_counter()

import argparse          # noqa: E402
import contextlib        # noqa: E402
import copy              # noqa: E402
import gc                # noqa: E402
import json              # noqa: E402
import math              # noqa: E402
import os                # noqa: E402
import shutil            # noqa: E402
import sys               # noqa: E402
import tempfile          # noqa: E402

import numpy as np       # noqa: E402

# `python3 benchmarks/run.py` puts benchmarks/ first on the path; the
# packages are `benchmarks.*` and `auron_tpu`, both under the checkout's root
_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if not __package__:
    sys.path[0] = _ROOT

from benchmarks.harness import cells, compare, loop   # noqa: E402

EXECUTE_MARK = "execute"
WARMUP_MAX = 5


def log(msg: str) -> None:
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)


def find_devices(chips: int, rehearse_cpu: bool):
    import jax
    devs = jax.devices()
    if rehearse_cpu:
        if len(devs) < chips:
            raise SystemExit(f"benchmark: rehearsal needs {chips} devices, "
                             f"JAX reports {len(devs)}")
        return devs
    if devs[0].platform != "tpu":
        raise SystemExit(f"benchmark: needs a TPU, JAX reports "
                         f"{devs[0].platform!r}; there is no CPU mode")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell needs {chips} chips, JAX "
                         f"reports {len(devs)}")
    return devs


def new_session():
    """A fresh session, as `chip_smoke.new_session` makes one.  PyArrowEngine
    is the system's own host engine, never the reference."""
    from auron_tpu.frontend.session import AuronSession
    from auron_tpu.it.oracle import PyArrowEngine
    return AuronSession(foreign_engine=PyArrowEngine())


def warm_up(execute_plan, n_plans, compile_log, phases) -> int:
    """Executes every plan until one execute of it hands no program to the
    backend: every shape of the cell's traffic is then compiled and the
    guard ladder's rung learned.  Returns how many executes it took."""
    total = 0
    for k in range(n_plans):
        for n in range(1, WARMUP_MAX + 1):
            before = compile_log.snapshot()
            execute_plan(k)
            after = compile_log.snapshot()
            total += 1
            phases[f"plan_{k}_execute_{n}"] = time.perf_counter() - T_START
            if n > 1 and after["programs"] == before["programs"] \
                    and after["traces"] == before["traces"]:
                break
        else:
            raise SystemExit(f"benchmark: plan {k} still compiling after "
                             f"{WARMUP_MAX} warm-up executes")
    return total


def replace_files(paths) -> None:
    """New modification times, as a writer that has replaced the files
    leaves them: what the program cached of them no longer applies."""
    for p in paths:
        st = os.stat(p)
        later = max(time.time_ns(), st.st_mtime_ns + 1_000_000)
        os.utime(p, ns=(later, later))


def observe_execute(ex, spans_out, records_out) -> None:
    """The program's spans and query record of one traced execute."""
    if ex.error is not None:
        return
    from auron_tpu.runtime import tracing
    by_name = {}
    if ex.result.trace is not None:
        for s in ex.result.trace.snapshot():
            if s.dur_ns >= 0:
                by_name[s.name] = by_name.get(s.name, 0.0) + s.dur_ns
    spans_out.append(by_name)
    rec = tracing.find_query(ex.result.query_id)
    if rec is not None:
        records_out.append({
            "retries": rec.retries, "fallbacks": rec.fallbacks,
            "num_fallbacks": rec.metric_totals.get("num_fallbacks", 0)})


def program_marks(window, trace):
    """The program's own spans of each traced execute, moved onto the
    profiler's clock (by the execute's own mark) so that idle gaps can be
    named after what the host was doing inside the program."""
    marks = [m for m in trace.host_marks if m[0] == EXECUTE_MARK]
    out = []
    for ex, (_name, mark_start, _dur) in zip(window.executes, marks):
        rec = getattr(ex.result, "trace", None)
        if rec is None:
            continue
        # the session opens its `query` span first thing in execute()
        spans = rec.snapshot()
        query = [s for s in spans if s.name == "query"]
        if not query:
            continue
        shift = mark_start - query[0].t0_ns
        out.extend((s.name, s.t0_ns + shift, float(s.dur_ns))
                   for s in spans if s.dur_ns > 0 and s.name != "query")
    return sorted(out, key=lambda e: e[1])


def algorithmic_bytes(cat, query, result_table) -> int:
    """Every column the plan's scans project, read once, plus the result
    written once (Arrow's in-memory widths; counted from the cell's files,
    never from the program)."""
    return sum(cat.tables[t].column_bytes[c]
               for t, cols in query.SCANS.items() for c in cols) \
        + result_table.nbytes


def check_window(window, issued, want_of, limits):
    """Every table the window returned against the reference of the
    parameters it was asked with: each number compared beside its limit (the
    worst reading over the executes), and how many executes failed (raised,
    or returned a table outside a limit)."""
    done = window.completed
    readings = [compare.compare_tables(ex.result.table, want_of(k))
                for ex, k in zip(window.executes, issued)
                if ex.error is None]
    wrong = sum(not compare.judge(r, limits)["ok"] for r in readings)
    failed = len(window.executes) - len(done) + wrong
    worst = compare.worst_of(readings) if readings \
        else {k: math.inf for k in limits}
    checks = {k: {"value": finite(c["value"]), "limit": c["limit"]}
              for k, c in compare.judge(worst, limits)["checks"].items()}
    checks["executes_failed"] = {"value": failed, "limit": 0}
    return checks, failed


def read_trace(trace_dir, window):
    """The profiler's trace of the window, reduced (harness/xtrace.py)."""
    from benchmarks.harness import xtrace
    trace = xtrace.read_xplane(trace_dir, [EXECUTE_MARK])
    log("device trace lines: " + "; ".join(trace.inventory))
    trace.host_marks = sorted(trace.host_marks + program_marks(window, trace),
                              key=lambda e: e[1])
    return xtrace.reduce(trace, EXECUTE_MARK, "between-executes")


class GcPauses:
    """Seconds the interpreter's collector held the host inside the window,
    by generation: for the run's log, to tell a collector pause from a
    stall of the program."""

    def __init__(self):
        self.seconds = {0: 0.0, 1: 0.0, 2: 0.0}
        self.longest = 0.0
        self._t0 = 0.0

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        else:
            took = time.perf_counter() - self._t0
            self.seconds[info["generation"]] += took
            self.longest = max(self.longest, took)


def finite(x: float) -> float:
    return x if math.isfinite(x) else 1e308


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), required=True)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args()
    run_cell(cells.load_cell(args.workload), args.seed, args.seconds,
             bool(args.trace), args.rehearse_cpu)
    return 0


def run_cell(cell, seed: int, seconds: float, traced: bool,
             rehearse_cpu: bool, faults=None) -> dict:
    """Runs the cell, prints the result's line and returns it.  `faults`,
    for benchmarks/selfcheck alone: {"catalog_for_plan": f(cat), "answer":
    f(table) -> table}, planted under the timed path."""
    faults = faults or {}
    devs = find_devices(cell.chips, rehearse_cpu)
    import jax
    import auron_tpu  # noqa: F401  (enables x64)
    from auron_tpu import config as program_config
    from benchmarks.harness import datagen, layer_metrics, peaks
    from benchmarks.harness.compile_log import CompileLog

    phases = {"imports_and_device": time.perf_counter() - T_START}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    used = devs[:cell.chips]
    compile_log = CompileLog()
    rows = cell.config["rehearse_rows" if rehearse_cpu else "rows"]
    param_sets = cell.traffic["param_sets"]
    # every run takes the same parameter sets, in an order of its seed's
    order = [int(k) for k in np.random.default_rng(abs(seed)).permutation(
        len(param_sets))]
    mesh = None
    if cell.config["mesh_devices"] > 1:
        from auron_tpu.parallel.mesh import data_mesh
        mesh = data_mesh(cell.config["mesh_devices"])

    work_dir = tempfile.mkdtemp(prefix="auron-bench-")   # under TMPDIR
    try:
        cat = datagen.generate(os.path.join(work_dir, "data"),
                               cell.query.SCANS, rows,
                               cell.config["data_seed"], seed)
        phases["data"] = time.perf_counter() - T_START
        plan_cat = cat
        if "catalog_for_plan" in faults:
            plan_cat = copy.deepcopy(cat)
            faults["catalog_for_plan"](plan_cat)
        plans = [cell.query.build_plan(plan_cat, p) for p in param_sets]
        replaced = [p for t in cell.traffic["replaced_before_each_execute"]
                    for p in cat.tables[t].chunks]
        session = new_session()
        issued = []          # the parameter set of each execute of the window

        def execute_plan(k):
            replace_files(replaced)
            res = session.execute(plans[k], mesh=mesh)
            if "answer" in faults:
                res.table = faults["answer"](res.table)
            return res

        def execute_once():
            issued.append(order[len(issued) % len(order)])
            return execute_plan(issued[-1])

        warmups = warm_up(execute_plan, len(plans), compile_log, phases)
        log(f"{cell.name}: rows {rows}; set-up phases ended at (s) "
            f"{ {k: round(v, 1) for k, v in phases.items()} }; {warmups} "
            f"warm-up executes; set-up compiled {compile_log.snapshot()}")

        spans, records = [], []
        annotate = contextlib.nullcontext
        tracing_on = contextlib.nullcontext()
        observe = None
        if traced:
            annotate = jax.profiler.TraceAnnotation
            tracing_on = program_config.conf.scoped(
                {"auron.trace.enable": True})
            observe = lambda ex: observe_execute(ex, spans, records)  # noqa: E731
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            trace_dir = os.path.join(work_dir, "trace")
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        # what set-up left behind (thousands of traced objects) is collected
        # here, not by a full collection inside the window
        gc.collect()
        gc.freeze()
        compiled_before = compile_log.snapshot()
        setup_s = time.perf_counter() - T_START

        pauses = GcPauses()
        gc.callbacks.append(pauses)
        with tracing_on:
            window = loop.closed_loop(execute_once, seconds, annotate,
                                      observe)
        gc.callbacks.remove(pauses)

        compiled_after = compile_log.snapshot()
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in used)
        trace_summary = {}
        if traced:
            jax.profiler.stop_trace()
            trace_summary = read_trace(trace_dir, window)

        # correctness: the plain reference runs now, after the window and
        # the memory reading, and outside `setup_s`
        done = window.completed
        t_ref = time.perf_counter()
        wants = {}

        def want_of(k):
            if k not in wants:
                wants[k] = cell.query.reference(cat.read, param_sets[k])
            return wants[k]

        checks, failed = check_window(window, issued, want_of,
                                      cell.query.LIMITS)
        reference_s = time.perf_counter() - t_ref
        correct = all(c["value"] <= c["limit"] for c in checks.values())

        walls = [e.wall_s for e in done]
        measured = {}
        if done:
            measured = {"query_s": window.elapsed_s / len(done),
                        "query_s.p95": loop.nearest_rank(walls, 95),
                        "setup_s": setup_s}
        rows_scanned = sum(cat.tables[t].rows for t in cell.query.SCANS)
        log(f"{len(done)} executes in {window.elapsed_s:.3f} s; first walls "
            f"{[round(w, 4) for w in walls[:10]]}; reference "
            f"{reference_s:.1f} s; collector pauses in the window by "
            f"generation { {g: round(v, 4) for g, v in pauses.seconds.items()} }"
            f", longest {pauses.longest:.4f} s; "
            f"rows scanned per execute {rows_scanned}")
        if done and not rehearse_cpu:
            print(json.dumps({"rows_per_s_per_chip": rows_scanned / (
                measured["query_s"] * cell.chips)}), flush=True)

        metrics = {}
        if not traced:
            for m in cell.end_to_end:
                if m["name"] in measured:
                    metrics[m["name"]] = {"value": measured[m["name"]],
                                          "unit": m["unit"]}
        else:
            ob = layer_metrics.Observed(
                executes=len(done), spans=spans,
                records=records,
                compiles={k: compiled_after[k] - compiled_before[k]
                          for k in compiled_after},
                trace=trace_summary, chips=cell.chips)
            if not rehearse_cpu:
                # device numbers exist only on the device
                ob.memory_peak_bytes = peak
                ob.hbm_bytes_per_s = peaks.peaks_of(
                    device["kind"])["hbm_bytes_per_s"]
                if done:
                    ob.algorithmic_bytes = algorithmic_bytes(
                        cat, cell.query, done[0].result.table)
            for m in cell.per_layer:
                value = layer_metrics.read_metric(
                    cells.metric_spec(m["name"]), ob)
                if value is not None:
                    metrics[m["name"]] = {"value": value, "unit": m["unit"]}

        if not rehearse_cpu:
            device["memory_peak_bytes"] = peak
        result = {"correct": bool(correct), "attempted": len(window.executes),
                  "failed": failed, "metrics": metrics, "device": device}
        if traced and trace_summary and not rehearse_cpu:
            device["busy_s"] = trace_summary["busy_s"]
            device["window_s"] = trace_summary["window_s"]
            result["breakdown"] = trace_summary["breakdown"]
        result["checks"] = checks
        for ex in window.executes:
            if ex.error:
                log(f"execute raised: {ex.error}")
        for k, c in result["checks"].items():
            print(f"check {k}: {c['value']!r} (limit {c['limit']!r})",
                  file=sys.stderr)
        sys.stderr.flush()
        print(json.dumps(result), flush=True)
        return result
    finally:
        shutil.rmtree(work_dir, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())

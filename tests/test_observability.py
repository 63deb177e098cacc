"""Observability layer tests: the span recorder + Chrome-trace export
(runtime/tracing.py), EXPLAIN ANALYZE (runtime/explain_analyze.py) with
its committed golden, query-id correlation through task_logging and the
task pool, the `latency` fault kind, and the trace CLI.

The HTTP export surface (/metrics Prometheus view, /queries) is covered
in tests/test_profiling_http.py."""

import json
import logging
import os
import time

import pytest

from auron_tpu.config import conf
from auron_tpu.it.datagen import generate
from auron_tpu.runtime import tracing
from auron_tpu.runtime.metrics import MetricNode

GOLDEN_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "golden_plans")

# serial per-partition path: exchanges/spills/tasks materialize, so the
# shuffle/task span families and per-operator metric trees exist (the
# single-device SPMD stage program has neither); parallelism pinned so
# fault-injection draw order is reproducible
SERIAL = {"auron.spmd.singleDevice.enable": False,
          "auron.task.parallelism": 1}


@pytest.fixture(scope="module")
def catalog(tmp_path_factory):
    return generate(str(tmp_path_factory.mktemp("obs_tpcds")), sf=0.002,
                    fact_chunks=3)


def _execute(name, catalog, extra_conf=None):
    from auron_tpu.frontend.session import AuronSession
    from auron_tpu.it import queries
    from auron_tpu.it.oracle import PyArrowEngine
    scope = dict(SERIAL)
    scope.update(extra_conf or {})
    plan = queries.build(name, catalog)
    with conf.scoped(scope):
        session = AuronSession(foreign_engine=PyArrowEngine())
        return session.execute(plan)


# fault-free q03 result shared between the golden test and the traced
# chaos test (one serial execution instead of two — tier-1 budget)
_BASELINE = {}


def _baseline_q03(catalog):
    if "q03" not in _BASELINE:
        _BASELINE["q03"] = _execute("q03", catalog)
    return _BASELINE["q03"]


# ---------------------------------------------------------------------------
# recorder unit tests
# ---------------------------------------------------------------------------

def test_span_noop_when_disabled():
    assert tracing.current_recorder() is None
    s = tracing.span("anything", cat="x")
    assert s is tracing.span("other")     # the shared no-op singleton
    with s:
        pass
    tracing.event("nothing")              # must not raise or record


def test_recorder_spans_and_export():
    rec = tracing.TraceRecorder("qtest", max_events=100)
    with tracing.trace_scope(recorder=rec, query_id="qtest"):
        assert tracing.current_query_id() == "qtest"
        with tracing.span("outer", cat="t", k=1):
            with tracing.span("inner", cat="t"):
                pass
        tracing.event("marker", cat="t", note="hi")
    assert tracing.current_recorder() is None
    names = [s.name for s in rec.snapshot()]
    assert names == ["inner", "outer", "marker"]   # close order
    doc = rec.to_chrome_trace()
    assert tracing.validate_chrome_trace(doc) == []
    xs = [e for e in doc["traceEvents"] if e.get("ph") == "X"]
    inst = [e for e in doc["traceEvents"] if e.get("ph") == "i"]
    assert {e["name"] for e in xs} == {"inner", "outer"}
    assert inst[0]["name"] == "marker" and inst[0]["args"]["note"] == "hi"
    # containment: inner nests inside outer on the timeline
    outer = next(e for e in xs if e["name"] == "outer")
    inner = next(e for e in xs if e["name"] == "inner")
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert json.loads(json.dumps(doc))   # JSON-serializable end to end


def test_recorder_error_spans_capture_exception():
    rec = tracing.TraceRecorder("qerr", max_events=10)
    with tracing.trace_scope(recorder=rec):
        with pytest.raises(ValueError):
            with tracing.span("boom"):
                raise ValueError("nope")
    (s,) = rec.snapshot()
    assert s.args and "ValueError: nope" in s.args["error"]


def test_recorder_bounded_drops():
    rec = tracing.TraceRecorder("qb", max_events=3)
    with tracing.trace_scope(recorder=rec):
        for _ in range(5):
            tracing.event("e")
    assert len(rec.snapshot()) == 3 and rec.dropped == 2
    assert rec.to_chrome_trace()["otherData"]["dropped_events"] == 2


def test_validate_rejects_malformed():
    assert tracing.validate_chrome_trace([]) != []
    assert tracing.validate_chrome_trace({}) != []
    errs = tracing.validate_chrome_trace({"traceEvents": [
        {"name": "", "ph": "Z", "ts": -5},
        {"name": "x", "ph": "X", "ts": 0.0},      # missing dur
        "not-an-object",
    ]})
    assert len(errs) >= 3


def test_summarize_critical_path():
    rec = tracing.TraceRecorder("qs", max_events=100)
    with tracing.trace_scope(recorder=rec):
        with tracing.span("root"):
            with tracing.span("child"):
                time.sleep(0.01)
    text = tracing.summarize_chrome_trace(rec.to_chrome_trace())
    assert "critical path:" in text
    assert "root" in text and "child" in text


def test_summarize_prints_self_time_by_parent():
    """Self time is a span's duration less its children's, by the
    recorded `parent`: the decomposition the per-layer metrics give."""
    def ev(name, ts, dur, id_, parent, tid=1):
        return {"name": name, "ph": "X", "ts": ts, "dur": dur, "pid": 1,
                "tid": tid, "args": {"id": id_, "parent": parent}}
    doc = {"traceEvents": [
        ev("task.execute", 0, 10_000, 1, 0),
        ev("sort.run", 1_000, 6_000, 2, 1),
        ev("sort.keys", 1_500, 4_000, 3, 2),
        ev("project.eval", 8_000, 1_000, 4, 1),
        # a child on another thread ran beside its parent
        ev("scan.decode", 2_000, 9_000, 5, 1, tid=2),
        {"name": "foreign", "ph": "X", "ts": 0, "dur": 500, "pid": 1,
         "tid": 1}]}
    lines = tracing.summarize_chrome_trace(doc).splitlines()
    assert lines[1].split() == ["name", "count", "total_ms", "self_ms",
                                "max_ms"]
    own = {ln.split()[0]: float(ln.split()[3]) for ln in lines[2:8]}
    assert own == {"task.execute": 3.0, "sort.run": 2.0, "sort.keys": 4.0,
                   "project.eval": 1.0, "scan.decode": 9.0, "foreign": 0.5}


# ---------------------------------------------------------------------------
# correlation key: query id through logging + task pool
# ---------------------------------------------------------------------------

def test_query_id_in_log_prefix():
    from auron_tpu.runtime import task_logging
    f = task_logging.TaskContextFilter()
    rec = logging.LogRecord("auron_tpu.test", logging.INFO, __file__, 1,
                            "hello", (), None)
    with tracing.trace_scope(query_id="abc123") as sc:
        with task_logging.task_scope(3, 7):
            f.filter(rec)
            assert rec.task == "[q abc123 stage 3 part 7] "
            assert task_logging.current_ids() == ("abc123", 3, 7)
        f.filter(rec)
        assert rec.task == "[q abc123] "
        assert sc.query_id == "abc123"
    f.filter(rec)
    assert rec.task == ""
    assert task_logging.current_ids() == (None, None, None)


def test_task_pool_propagates_query_context():
    from auron_tpu.runtime.task_pool import run_tasks
    rec = tracing.TraceRecorder("qpool", max_events=1000)

    def work(i):
        with tracing.span("work", idx=i):
            pass
        return tracing.current_query_id()

    with conf.scoped({"auron.task.parallelism": 4}):
        with tracing.trace_scope(recorder=rec, query_id="qpool"):
            out = run_tasks(work, range(8))
    # every worker thread saw the query id AND recorded into the same
    # recorder (contextvars copied per task by run_tasks)
    assert out == ["qpool"] * 8
    spans = [s for s in rec.snapshot() if s.name == "work"]
    assert len(spans) == 8
    assert sorted(s.args["idx"] for s in spans) == list(range(8))


# ---------------------------------------------------------------------------
# the latency fault kind
# ---------------------------------------------------------------------------

def test_latency_fault_sleeps_not_raises():
    from auron_tpu import faults
    spec = "slow.point:latency:ms=40,max=2"
    faults.reset(spec)
    with conf.scoped({"auron.faults.spec": spec}):
        t0 = time.perf_counter()
        faults.fault_point("slow.point")     # sleeps, must NOT raise
        dt = time.perf_counter() - t0
        assert dt >= 0.035
        faults.fault_point("slow.point")
        t0 = time.perf_counter()
        faults.fault_point("slow.point")     # max=2: no injection left
        assert time.perf_counter() - t0 < 0.02
        reg = faults.active_registry()
        assert reg.counts()["slow.point"] == (3, 2)


def test_latency_fault_spec_params():
    from auron_tpu.faults import FaultSpecError, parse_spec
    (r,) = parse_spec("spill.write:latency:ms=12.5,p=0.5,seed=3")
    assert r.kind == "latency" and r.delay_ms == 12.5 and r.p == 0.5
    with pytest.raises(FaultSpecError):
        parse_spec("x:latency:ms=abc")


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE units
# ---------------------------------------------------------------------------

def _tree(rows):
    root = MetricNode("ProjectExec")
    root.add("output_rows", rows)
    root.add("elapsed_compute_ns", 1000)
    child = root.child("ScanExec")
    child.add("output_rows", rows * 2)
    return root


def test_merge_metric_trees_sums_by_structure():
    from auron_tpu.runtime.explain_analyze import (
        merge_metric_trees, metric_totals,
    )
    other = MetricNode("SortExec")
    other.add("output_rows", 5)
    merged = merge_metric_trees([_tree(10), _tree(20), other])
    assert len(merged) == 2
    (t, n), (o, m) = merged
    assert n == 2 and t.values["output_rows"] == 30
    assert t.children[0].values["output_rows"] == 60
    assert m == 1 and o.values["output_rows"] == 5
    totals = metric_totals([_tree(10), _tree(20), other])
    assert totals["output_rows"] == 10 + 20 + 20 + 40 + 5
    assert totals["elapsed_compute_ns"] == 2000


def test_explain_analyze_normalize_drops_volatile():
    from auron_tpu.runtime.explain_analyze import explain_analyze
    human = explain_analyze([_tree(10)], query_id="q1", wall_s=1.5,
                            rows=10)
    assert "q1" in human and "wall=1.500s" in human
    assert "compute=0.0ms" in human
    canon = explain_analyze([_tree(10)], query_id="q1", wall_s=1.5,
                            rows=10, normalize=True)
    assert "q1" not in canon and "wall" not in canon
    assert "_ns" not in canon and "compute" not in canon
    assert "output_rows=10" in canon


def test_explain_analyze_spmd_message():
    from auron_tpu.runtime.explain_analyze import explain_analyze
    text = explain_analyze([], spmd=True, rows=3)
    assert "SPMD stage program" in text and "mode=spmd" in text


def test_explain_analyze_fused_fragment_boundary():
    """A fused row-local chain renders as ONE FusedFragmentExec node in
    the EXPLAIN ANALYZE tree (the fragment boundary the issue asks
    for)."""
    import pyarrow as pa

    from auron_tpu.ir import expr as E
    from auron_tpu.ir import plan as P
    from auron_tpu.ir.expr import col, lit
    from auron_tpu.ir.schema import from_arrow_schema
    from auron_tpu.runtime.executor import execute_plan
    from auron_tpu.runtime.explain_analyze import render_analyzed
    from auron_tpu.runtime.resources import ResourceRegistry

    table = pa.table({"x": list(range(100))})
    res = ResourceRegistry()
    res.put("src", table)
    plan = P.Projection(
        child=P.Filter(
            child=P.FFIReader(schema=from_arrow_schema(table.schema),
                              resource_id="src"),
            predicates=(E.BinaryExpr(left=col("x"), op=">",
                                     right=lit(10)),)),
        exprs=(col("x"),), names=("x",))
    out = execute_plan(plan, resources=res)
    assert out.to_table().num_rows == 89
    text = render_analyzed([out.metrics], normalize=True)
    assert "FusedFragmentExec" in text
    _check_golden("fused_chain", text + "\n")


def _check_golden(name: str, text: str) -> None:
    path = os.path.join(GOLDEN_DIR, f"{name}.analyze.txt")
    if os.environ.get("AURON_REGEN_GOLDEN") == "1":
        with open(path, "w") as f:
            f.write(text)
        return
    assert os.path.exists(path), \
        f"no golden at {path} (regen with AURON_REGEN_GOLDEN=1)"
    with open(path) as f:
        golden = f.read()
    assert golden == text, \
        (f"EXPLAIN ANALYZE for {name} deviates from {path} "
         f"(AURON_REGEN_GOLDEN=1 to approve):\n--- golden\n{golden}"
         f"\n--- actual\n{text}")


# ---------------------------------------------------------------------------
# end-to-end: golden + traced chaos run on a TPC-DS query
# ---------------------------------------------------------------------------

def test_explain_analyze_golden_q03(catalog):
    """Acceptance: EXPLAIN ANALYZE for a TPC-DS query matches the
    committed golden with 0 verifier errors; tracing off leaves no
    recorder on the result."""
    from auron_tpu.it import stability
    res = _baseline_q03(catalog)
    assert res.trace is None                      # tracing off (default)
    assert res.query_id and res.wall_s > 0        # but the id is minted
    assert stability.lint_converted(res.converted, res.ctx) is None
    _check_golden("q03", res.explain_analyze(normalize=True) + "\n")
    # the human form carries the volatile fields the canonical drops
    human = res.explain_analyze()
    assert res.query_id in human and "compute=" in human


@pytest.mark.slow
def test_traced_query_spans_and_latency(catalog, tmp_path):
    """Acceptance + chaos-trace satellite: a traced TPC-DS run exports
    valid Chrome-trace JSON containing the convert/fuse/compile/execute/
    shuffle/retry span families, injected latency is visible as span
    durations, and the result matches the fault-free run."""
    from auron_tpu.ops import kernel_cache

    baseline = _baseline_q03(catalog)
    # a cleared kernel cache forces jitted-program builds so the
    # compile-family events provably appear in the trace
    kernel_cache.clear()
    spec = ("shuffle.push:io:p=1,max=1,seed=5;"
            "shuffle.push:latency:ms=60,max=2,after=1,seed=9")
    from auron_tpu import faults
    faults.reset(spec)
    res = _execute("q03", catalog, {
        "auron.trace.enable": True,
        "auron.faults.spec": spec,
        "auron.retry.backoff.base.ms": 1.0,
        "auron.retry.backoff.max.ms": 5.0,
    })
    assert res.trace is not None
    doc = res.trace.to_chrome_trace()
    assert tracing.validate_chrome_trace(doc) == []
    events = doc["traceEvents"]
    names = {e["name"] for e in events}
    # the lifecycle span families the acceptance names
    assert {"query", "plan.convert", "plan.fuse", "plan.verify",
            "task.execute", "shuffle.push", "shuffle.fetch",
            "exchange.map", "op.complete"} <= names
    assert "kernel.build" in names or "fragment.compile" in names
    assert "retry" in names                        # the injected io fault
    retry_ev = next(e for e in events if e["name"] == "retry")
    assert "injected io fault" in retry_ev["args"]["error"]
    # injected latency stretches the instrumented span's duration
    pushes = [e for e in events
              if e["name"] == "shuffle.push" and e.get("ph") == "X"]
    assert pushes and max(p["dur"] for p in pushes) >= 60_000 * 0.9
    # slowness, not failure: the answer is still bit-identical
    assert res.table.sort_by([(c, "ascending")
                              for c in res.table.column_names]).equals(
        baseline.table.sort_by([(c, "ascending")
                                for c in baseline.table.column_names]))
    # the query landed in the history ring with its trace
    rec = tracing.find_query(res.query_id)
    assert rec is not None and rec.trace is not None
    assert rec.rows == res.table.num_rows and rec.attempts > 0
    # save + CLI round trip (validate and summarize the dumped file)
    import auron_tpu.trace as trace_cli
    path = res.trace.save(str(tmp_path / "q03.trace.json"))
    assert trace_cli.main(["validate", path]) == 0
    assert trace_cli.main(["summary", path, "--top", "5"]) == 0


@pytest.mark.slow
def test_tools_trace_check_script():
    """tools/trace_check.sh is the CI trace gate; keep it green from
    pytest so a pipeline that only runs the suite still exercises it."""
    import shutil
    import subprocess
    import sys
    script = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "trace_check.sh")
    if not os.path.exists(script) or shutil.which("bash") is None:
        pytest.skip("trace script or bash unavailable")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(sys.path))
    out = subprocess.run(["bash", script], capture_output=True,
                         text=True, timeout=540, env=env)
    assert out.returncode == 0, out.stdout + out.stderr

"""Memory-observability tests (memmgr/manager.py accounting layer):
peak/watermark tracking, spill attribution, the self-spill counting
bugfix, `mem.pressure`/`mem.spill` trace events, the `mem` fault kind
with its chaos-style bit-identity gate, per-operator memory columns in
EXPLAIN ANALYZE, per-query memory totals in the history ring, and the
query-diff machinery.

The HTTP export surface (/memory, /queries/diff, the new Prometheus
gauges) is covered in tests/test_profiling_http.py."""

import os
import subprocess

import numpy as np
import pyarrow as pa
import pytest

from auron_tpu.config import conf
from auron_tpu.memmgr.manager import (
    MemConsumer, MemManager, get_manager, reset_manager,
)
from auron_tpu.runtime import tracing
from auron_tpu.runtime.metrics import MetricNode

GOLDEN_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                          "golden_plans")

TINY_TRIGGER = {"auron.memory.spill.min.trigger.bytes": 1}


@pytest.fixture(autouse=True)
def _fresh_manager():
    """Every test in this module mutates the global manager; leave a
    clean default-budget instance behind."""
    yield
    from auron_tpu import faults
    faults.reset()
    reset_manager()


class FakeConsumer(MemConsumer):
    """Spill releases everything and logs the reported freed bytes —
    the ground truth the attribution invariant compares against."""

    def __init__(self, name, spillable=True, sticky=False):
        super().__init__(name, spillable)
        self.freed_log = []
        self.sticky = sticky    # a consumer that refuses to spill

    def spill(self):
        if self.sticky:
            self.freed_log.append(0)
            return 0
        freed = self.mem_used
        self.freed_log.append(freed)
        self.update_mem_used(0)
        return freed


# ---------------------------------------------------------------------------
# accounting invariants
# ---------------------------------------------------------------------------

def test_peak_tracking_consumer_and_pool():
    with conf.scoped(TINY_TRIGGER):
        mgr = reset_manager(10_000)
        a = mgr.register_consumer(FakeConsumer("A"))
        a.update_mem_used(700)
        a.update_mem_used(300)
        assert a.mem_peak == 700 and a.mem_used == 300
        assert mgr.peak_used == 700
        b = mgr.register_consumer(FakeConsumer("B"))
        b.update_mem_used(600)
        assert mgr.peak_used == 900
        mgr.unregister_consumer(a)
        mgr.unregister_consumer(b)
        # cumulative per-name stats survive unregistration
        totals = mgr.consumer_totals()
        assert totals["A"]["peak"] == 700 and totals["B"]["peak"] == 600
        assert mgr.stats()["peak_used"] == 900     # pool peak is sticky


def test_watermark_crossings_fire_once_in_order():
    with conf.scoped(dict(TINY_TRIGGER)):
        mgr = reset_manager(1000)
        c = mgr.register_consumer(FakeConsumer("C"))
        c.update_mem_used(400)          # below 0.5
        assert mgr.stats()["watermarks_crossed"] == []
        c.update_mem_used(600)          # crosses 0.5
        c.update_mem_used(100)          # dip: must not re-arm
        c.update_mem_used(990)          # crosses 0.8 and 0.95 at once
        crossings = mgr.stats()["watermarks_crossed"]
        fracs = [x["fraction"] for x in crossings]
        assert fracs == [0.5, 0.8, 0.95]
        assert fracs == sorted(fracs)
        assert all(x["budget"] == 1000 for x in crossings)
        c.update_mem_used(995)          # nothing left to fire
        assert len(mgr.stats()["watermarks_crossed"]) == 3


def test_self_spill_fallback_is_counted_and_attributed():
    """The bugfix: the fallback path (arbitration target freed nothing,
    requester spills itself) historically spilled WITHOUT bumping
    num_spills; both paths must now count and attribute."""
    with conf.scoped(TINY_TRIGGER):
        mgr = reset_manager(1000)
        big = mgr.register_consumer(FakeConsumer("Sticky", sticky=True))
        big.update_mem_used(900)
        small = mgr.register_consumer(FakeConsumer("Requester"))
        small.update_mem_used(500)      # over budget; target = Sticky
        recs = mgr.spill_records()
        assert [r["path"] for r in recs] == ["arbitration", "fallback"]
        assert recs[0]["consumer"] == "Sticky"
        assert recs[1]["consumer"] == "Requester"
        assert all(r["requested_by"] == "Requester" for r in recs)
        assert mgr.num_spills == 2
        assert recs[1]["freed_bytes"] == 500 == small.freed_log[-1]
        assert mgr.stats()["spills_by_path"] == \
            {"arbitration": 1, "fallback": 1}


def test_spill_fuzz_attribution_invariants(rng):
    """Random updates under a tiny budget: (a) every consumer's peak >=
    its final usage, (b) attributed freed bytes equal the bytes the
    consumers themselves reported, (c) watermark events are monotone and
    unique, (d) the record ring agrees with the aggregate counters."""
    with conf.scoped(TINY_TRIGGER):
        mgr = reset_manager(50_000)
        consumers = [mgr.register_consumer(FakeConsumer(f"F{i}"))
                     for i in range(4)]
        for _ in range(120):
            c = consumers[int(rng.integers(len(consumers)))]
            c.update_mem_used(int(rng.integers(0, 30_000)))
        for c in consumers:
            assert c.mem_peak >= c.mem_used
        assert mgr.peak_used >= mgr.total_used
        assert mgr.num_spills > 0, "fuzz budget must force spills"
        recs = mgr.spill_records()
        assert len(recs) == mgr.num_spills <= mgr.MAX_SPILL_RECORDS
        by_name = {}
        for r in recs:
            by_name.setdefault(r["consumer"], 0)
            by_name[r["consumer"]] += r["freed_bytes"]
        for c in consumers:
            assert by_name.get(c.name, 0) == sum(c.freed_log), \
                f"attributed bytes for {c.name} != consumer-reported"
        assert sum(by_name.values()) == mgr.stats()["spill_bytes_freed"]
        fracs = [x["fraction"]
                 for x in mgr.stats()["watermarks_crossed"]]
        assert fracs == sorted(set(fracs))
        totals = mgr.consumer_totals()
        for c in consumers:
            assert totals[c.name]["freed_bytes"] == sum(c.freed_log)


def test_watermark_and_spill_trace_events():
    rec = tracing.TraceRecorder("qmem", max_events=1000)
    with conf.scoped(TINY_TRIGGER):
        mgr = reset_manager(1000)
        with tracing.trace_scope(recorder=rec, query_id="qmem"):
            c = mgr.register_consumer(FakeConsumer("SortExec"))
            c.update_mem_used(600)
            c.update_mem_used(1200)     # crosses the rest + spills
    spans = rec.snapshot()
    pressure = [s for s in spans if s.name == "mem.pressure"]
    spills = [s for s in spans if s.name == "mem.spill"]
    fracs = [s.args["fraction"] for s in pressure]
    assert fracs == sorted(fracs) and fracs[0] == 0.5
    assert all(s.args["consumer"] == "SortExec" for s in pressure)
    (sp,) = spills
    assert sp.args["consumer"] == "SortExec"
    assert sp.args["path"] == "self"
    assert sp.args["freed_bytes"] == 1200
    # exports as valid Chrome-trace instants
    assert tracing.validate_chrome_trace(rec.to_chrome_trace()) == []


@pytest.mark.parametrize("platform,stats,want", [
    ("tpu", {"bytes_limit": 16 << 30}, "fraction"),   # reported limit
    ("cpu", None, "4gb"),                             # CPU reports none
    ("tpu", None, "error"),           # an accelerator must never guess
    ("tpu", {}, "error"),
])
def test_default_budget_never_guesses_an_accelerator(monkeypatch, platform,
                                                     stats, want):
    import types

    import jax
    dev = types.SimpleNamespace(platform=platform, device_kind="fake",
                                memory_stats=lambda: stats)
    monkeypatch.setattr(jax, "devices", lambda *a: [dev])
    frac = float(conf.get("auron.memory.fraction"))
    if want == "error":
        with pytest.raises(RuntimeError, match="no memory limit"):
            MemManager._default_budget()
        # the explicit budget is the way out
        with conf.scoped({"auron.memory.budget.bytes": 123}):
            assert MemManager._default_budget() == 123
    else:
        size = (16 << 30) if want == "fraction" else (4 << 30)
        assert MemManager._default_budget() == int(size * frac)


def test_reservations_shrink_effective_budget():
    mgr = reset_manager(10_000)
    assert mgr.add_reservation("x", 4_000) == 6_000
    assert mgr.add_reservation("x", 1_000) == 5_000
    st = mgr.stats()
    assert st["reserved"] == 5_000 and st["effective_budget"] == 5_000
    mgr.release_reservations("x")
    assert mgr.stats()["reserved"] == 0


# ---------------------------------------------------------------------------
# the `mem` fault kind
# ---------------------------------------------------------------------------

def test_mem_fault_parse_and_reserve():
    from auron_tpu import faults
    (r,) = faults.parse_spec("site.x:mem:bytes=4000,max=1")
    assert r.kind == "mem" and r.mem_bytes == 4000
    (rf,) = faults.parse_spec("site.x:mem:frac=0.25")
    assert rf.mem_frac == 0.25 and rf.mem_bytes is None
    with pytest.raises(faults.FaultSpecError):
        faults.parse_spec("site.x:mem:bytes=abc")

    mgr = reset_manager(10_000)
    spec = "site.x:mem:bytes=4000,max=1"
    faults.reset(spec)
    with conf.scoped({"auron.faults.spec": spec}):
        faults.fault_point("site.x")        # reserves, must NOT raise
        assert mgr.stats()["reserved"] == 4000
        faults.fault_point("site.x")        # max=1: no further shrink
        assert mgr.stats()["reserved"] == 4000
        assert faults.active_registry().counts()["site.x"] == (2, 1)


def _sorted_table(n=30_000, seed=7):
    rng = np.random.default_rng(seed)
    return pa.table({"k": rng.integers(0, 1_000_000, n),
                     "v": rng.standard_normal(n)})


def _sort_plan(table):
    from auron_tpu.ir import plan as P
    from auron_tpu.ir.expr import SortExpr, col
    from auron_tpu.ir.schema import from_arrow_schema
    return P.Sort(
        child=P.FFIReader(schema=from_arrow_schema(table.schema),
                          resource_id="src"),
        sort_exprs=(SortExpr(child=col("k")), SortExpr(child=col("v"))))


def _run_sort(table):
    from auron_tpu.runtime.executor import execute_plan
    from auron_tpu.runtime.resources import ResourceRegistry
    res = ResourceRegistry()
    res.put("src", table)
    return execute_plan(_sort_plan(table), resources=res)


def test_chaos_mem_fault_bit_identical_under_pressure():
    """The chaos-style satellite gate: a query under injected memory
    pressure must spill — visibly (mem.pressure/mem.spill in the trace,
    attribution on the records) — and still produce a bit-identical
    result."""
    from auron_tpu import faults
    table = _sorted_table()
    reset_manager()
    baseline = _run_sort(table).to_table()

    spec = "op.execute:mem:bytes=999999999,max=1,seed=3"
    faults.reset(spec)
    rec = tracing.TraceRecorder("qchaosmem", max_events=100_000)
    with conf.scoped({"auron.faults.spec": spec,
                      "auron.memory.spill.min.trigger.bytes": 1024}):
        mgr = reset_manager(1_000_000)
        with tracing.trace_scope(recorder=rec, query_id="qchaosmem"):
            pressured = _run_sort(table).to_table()
    assert mgr.num_spills > 0, "reservation must force spill pressure"
    assert pressured.equals(baseline), \
        "memory pressure changed the result"
    names = [s.name for s in rec.snapshot()]
    assert "mem.pressure" in names and "mem.spill" in names
    spill_args = [s.args for s in rec.snapshot()
                  if s.name == "mem.spill"]
    assert all(a["consumer"] == "SortExec" for a in spill_args)
    recs = mgr.spill_records()
    assert sum(r["freed_bytes"] for r in recs) == \
        mgr.stats()["spill_bytes_freed"]


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE memory columns
# ---------------------------------------------------------------------------

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


def test_explain_analyze_memory_columns_and_golden():
    """A spilling sort renders mem_peak (human, dropped in canonical as
    a volatile byte count) and mem_spill_count (both modes) on the
    operator that owned the memory — and the canonical form is the
    committed golden."""
    from auron_tpu.runtime.explain_analyze import render_analyzed
    table = _sorted_table()
    with conf.scoped({"auron.memory.spill.min.trigger.bytes": 1024}):
        mgr = reset_manager(200_000)
        out = _run_sort(table)
    assert mgr.num_spills > 0
    human = render_analyzed([out.metrics])
    assert "mem_peak=" in human and "mem_spill_count=" in human
    canon = render_analyzed([out.metrics], normalize=True)
    assert "mem_spill_count=" in canon
    assert "mem_peak" not in canon and "mem_spill_size" not in canon
    _check_golden("spill_sort", canon + "\n")


def test_query_record_memory_totals_and_diff():
    """Session-level: two runs of one tiny plan — one unconstrained, one
    under a spill-forcing budget — land in the history ring with memory
    totals, and diff_metric_trees shows the spill delta per operator."""
    from auron_tpu.frontend import AuronSession, ForeignExpr, ForeignNode
    from auron_tpu.frontend import fcol
    from auron_tpu.ir.schema import DataType, Field, Schema
    from auron_tpu.runtime.explain_analyze import (
        diff_metric_trees, render_diff,
    )

    I64 = DataType.int64()
    schema = Schema((Field("k", I64),))
    rng = np.random.default_rng(11)
    rows = [{"k": int(v)} for v in rng.integers(0, 10_000, 4096)]
    src = ForeignNode("LocalTableScanExec", output=schema,
                      attrs={"rows": rows})
    plan = ForeignNode(
        "SortExec", children=(src,), output=schema,
        attrs={"sort_order": [
            ForeignExpr("SortOrder", children=(fcol("k", I64),),
                        attrs={"asc": True, "nulls_first": True})]})
    scope = {"auron.spmd.singleDevice.enable": False,
             "auron.task.parallelism": 1}
    with conf.scoped(scope):
        session = AuronSession()
        reset_manager()
        res_a = session.execute(plan)
        with conf.scoped({"auron.memory.spill.min.trigger.bytes": 256}):
            reset_manager(8_000)
            res_b = session.execute(plan)
    reset_manager()
    assert res_a.table.equals(res_b.table)
    rec_a = tracing.find_query(res_a.query_id)
    rec_b = tracing.find_query(res_b.query_id)
    assert rec_a.mem_spills == 0
    assert rec_b.mem_spills > 0 and rec_b.mem_spill_bytes > 0
    assert rec_b.mem_peak > 0
    assert rec_b.to_dict()["mem_spills"] == rec_b.mem_spills
    assert rec_a.metric_trees and rec_b.metric_trees
    diff = diff_metric_trees(rec_a.metric_trees, rec_b.metric_trees)
    assert diff["unmatched_a"] == 0 and diff["unmatched_b"] == 0
    sort_nodes = [n for g in diff["groups"] for n in g["nodes"]
                  if n["name"] == "SortExec"]
    assert sort_nodes, "diff must pair the SortExec operator"
    spill_delta = sort_nodes[0]["metrics"].get("mem_spill_count")
    assert spill_delta and spill_delta["delta"] > 0
    text = render_diff(diff, res_a.query_id, res_b.query_id)
    assert "SortExec" in text and "mem_spill_count=" in text


# ---------------------------------------------------------------------------
# diff machinery units
# ---------------------------------------------------------------------------

def _tree_dicts(rows, spills=0):
    root = MetricNode("ProjectExec")
    root.add("output_rows", rows)
    child = root.child("SortExec")
    child.add("output_rows", rows)
    if spills:
        child.add("mem_spill_count", spills)
    return [{"tasks": 2, "tree": root.to_dict()}]


def test_diff_metric_trees_deltas():
    from auron_tpu.runtime.explain_analyze import diff_metric_trees
    diff = diff_metric_trees(_tree_dicts(100), _tree_dicts(130, spills=3))
    (g,) = diff["groups"]
    assert g["tasks_a"] == g["tasks_b"] == 2
    by_name = {n["name"]: n for n in g["nodes"]}
    assert by_name["ProjectExec"]["metrics"]["output_rows"]["delta"] == 30
    assert by_name["SortExec"]["metrics"]["mem_spill_count"] == \
        {"a": 0, "b": 3, "delta": 3}
    assert by_name["SortExec"]["depth"] == 1


def test_diff_metric_trees_shape_mismatch():
    from auron_tpu.runtime.explain_analyze import diff_metric_trees
    other = [{"tasks": 1, "tree": MetricNode("AggExec").to_dict()}]
    with pytest.raises(ValueError, match="plan shape"):
        diff_metric_trees(_tree_dicts(10), other)


# ---------------------------------------------------------------------------
# CI script hook
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_tools_mem_check_script():
    """tools/mem_check.sh is the CI memory-observability gate; keep it
    green from pytest like chaos_check/trace_check."""
    import shutil
    import sys
    script = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "mem_check.sh")
    if not os.path.exists(script) or shutil.which("bash") is None:
        pytest.skip("mem script or bash unavailable")
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(sys.path))
    out = subprocess.run(["bash", script], capture_output=True,
                         text=True, timeout=540, env=env)
    assert out.returncode == 0, out.stdout + out.stderr


# ---------------------------------------------------------------------------
# spill-victim ranking (freed-bytes-per-wall-second, serving PR satellite)
# ---------------------------------------------------------------------------

def test_spill_victim_ranked_by_freed_rate():
    """Unit ranking contract (_pick_spill_victim): consumers with spill
    history rank by freed-bytes-per-wall-second; no-history consumers
    rank ABOVE measured ones (tried once to earn history) tie-broken by
    size; 'largest' restores the pure size policy."""
    with conf.scoped(TINY_TRIGGER):
        mgr = reset_manager(10_000)
        # fabricated history: Slow freed 1KB over 1s, Fast 1MB over 1ms
        mgr._by_name["Slow"] = {"registrations": 1, "peak": 0, "spills": 2,
                                "freed_bytes": 1000,
                                "wall_ns": 1_000_000_000}
        mgr._by_name["Fast"] = {"registrations": 1, "peak": 0, "spills": 2,
                                "freed_bytes": 1_000_000,
                                "wall_ns": 1_000_000}
        slow = mgr.register_consumer(FakeConsumer("Slow"))
        fast = mgr.register_consumer(FakeConsumer("Fast"))
        slow.mem_used = 5000      # bigger, but historically a bad victim
        fast.mem_used = 2000
        assert mgr._pick_spill_victim([slow, fast]) is fast
        # an unmeasured consumer is tried before any measured one
        new = mgr.register_consumer(FakeConsumer("Fresh"))
        new.mem_used = 1500
        assert mgr._pick_spill_victim([slow, fast, new]) is new
        # several unmeasured: largest-consumer fallback between them
        new2 = mgr.register_consumer(FakeConsumer("Fresh2"))
        new2.mem_used = 1600
        assert mgr._pick_spill_victim([slow, new, new2]) is new2
        with conf.scoped({"auron.memory.spill.victim.strategy":
                          "largest"}):
            assert mgr._pick_spill_victim([slow, fast, new]) is slow


def test_spill_victim_learns_from_history_end_to_end():
    """A consumer class that spills but frees nothing ('sticky') is
    chosen once (no history: largest-consumer), then sinks below a
    class with a real freed-rate — the arbitration stops hammering the
    victim that never helps."""
    with conf.scoped(TINY_TRIGGER):
        mgr = reset_manager(1000)
        sticky = mgr.register_consumer(FakeConsumer("Sticky",
                                                    sticky=True))
        sticky.update_mem_used(900)
        good = mgr.register_consumer(FakeConsumer("Good"))
        good.update_mem_used(500)    # over budget, nobody has history:
        # largest (Sticky) tried, freed 0 -> fallback self-spill of Good
        assert [r["consumer"] for r in mgr.spill_records()] == \
            ["Sticky", "Good"]
        # second pressure event: Good's positive rate now outranks the
        # bigger zero-rate Sticky — Sticky is left alone
        good.update_mem_used(600)
        last = mgr.spill_records()[-1]
        assert last["consumer"] == "Good"
        assert mgr.consumer_totals()["Sticky"]["spills"] == 1


def test_spill_victim_largest_strategy_preserved():
    """auron.memory.spill.victim.strategy=largest keeps the reference
    policy: the sticky big consumer keeps getting chosen."""
    with conf.scoped({**TINY_TRIGGER,
                      "auron.memory.spill.victim.strategy": "largest"}):
        mgr = reset_manager(1000)
        sticky = mgr.register_consumer(FakeConsumer("Sticky",
                                                    sticky=True))
        sticky.update_mem_used(900)
        good = mgr.register_consumer(FakeConsumer("Good"))
        good.update_mem_used(500)
        good.update_mem_used(600)
        targets = [r["consumer"] for r in mgr.spill_records()
                   if r["path"] == "arbitration"]
        assert targets == ["Sticky", "Sticky"]


# ---------------------------------------------------------------------------
# agg staged-state spilled mid-collapse (concurrent-pressure regression)
# ---------------------------------------------------------------------------

def _agg_plan(table):
    from auron_tpu.ir import plan as P
    from auron_tpu.ir.expr import AggExpr, col
    from auron_tpu.ir.schema import DataType, from_arrow_schema
    return P.Agg(
        child=P.FFIReader(schema=from_arrow_schema(table.schema),
                          resource_id="src"),
        exec_mode="single", grouping=(col("k"),), grouping_names=("k",),
        aggs=(AggExpr(fn="sum", children=(col("v"),),
                      return_type=DataType.float64()),),
        agg_names=("s",))


def _run_agg(table):
    from auron_tpu.runtime.executor import execute_plan
    from auron_tpu.runtime.resources import ResourceRegistry
    res = ResourceRegistry()
    res.put("src", table)
    return execute_plan(_agg_plan(table), resources=res)


@pytest.mark.slow   # PR 18 tier-1 re-split (9.4s; spill-metric
# plumbing stays covered by the other staged-spill tests)
def test_agg_staged_spilled_mid_collapse_not_lost(monkeypatch):
    """Serving-PR regression: with concurrent queries sharing the pool,
    the accounting update INSIDE AggExec._compact_staged can push usage
    over budget and arbitration may pick the agg itself — emptying
    _staged between the collapse and the read (_staged[0] IndexError,
    observed in the 8-query stress).  Simulate that exact window by
    spilling right after the first real collapse: the rows must come
    back through the spill-merge tail, bit-identical."""
    from auron_tpu.ops.agg.exec import AggExec

    table = _sorted_table(n=20_000)
    reset_manager()
    baseline = _canonical(_run_agg(table).to_table())

    fired = {"n": 0}
    orig = AggExec._compact_staged

    def compact_then_arbitrated_spill(self):
        orig(self)
        if fired["n"] == 0 and self._staged and not self._has_host_aggs:
            fired["n"] = 1
            # what manager arbitration does when it picks this consumer
            self.spill()

    with conf.scoped(TINY_TRIGGER):
        mgr = reset_manager(50_000_000)
        monkeypatch.setattr(AggExec, "_compact_staged",
                            compact_then_arbitrated_spill)
        out = _canonical(_run_agg(table).to_table())
    assert fired["n"] == 1, "the mid-collapse window never opened"
    assert out.equals(baseline), \
        "rows were lost when staged state spilled mid-collapse"


def _canonical(t):
    t = t.combine_chunks()
    return t.sort_by([(n, "ascending") for n in t.column_names]) \
        if t.num_rows and t.num_columns else t

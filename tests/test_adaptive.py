"""Adaptive query execution (runtime/adaptive.py + the session's
stage-boundary replan hook):

- AQE equivalence property: corpus + synthetic queries produce
  value-identical results with `auron.adaptive.enable` on vs off
  (serial here; the fleet variant is the slow-marked test below).
- Forced-decision unit tests: broadcast conversion (safe/unsafe join
  types), co-partitioned coalescing, synthetic-skew splitting, each
  asserting the structured decision AND the result equivalence.
- Rewritten plans are analyzer-clean (the `adaptive` contract pass
  runs in the default battery; a rewrite that fails verification is
  dropped, never executed).
- The unified CostModel: kernel half exposed, live exchange history,
  the cost-chosen filter-adjacency choice (PR 3 follow-up).
- Stage-boundary admission re-forecast: the ledger provably DROPS at a
  stage boundary for a query that turns out light.
- Exchange codec policy: local transports skip compression, remote
  transports keep the configured codec.
"""

import pyarrow as pa
import pytest

from auron_tpu import config
from auron_tpu.frontend import AuronSession, ForeignNode, fcol, flit
from auron_tpu.ir import plan as P
from auron_tpu.ir.schema import DataType, Field, Schema
from auron_tpu.it import compare, datagen, queries
from auron_tpu.it.oracle import PyArrowEngine
from auron_tpu.runtime import adaptive, counters

I64 = DataType.int64()
F64 = DataType.float64()
SALES = Schema((Field("k", I64), Field("v", F64)))
DIM = Schema((Field("k2", I64), Field("w", F64)))

SERIAL = {"auron.spmd.singleDevice.enable": False}
AQE = {**SERIAL, "auron.adaptive.enable": True}


class ToyEngine:
    def execute(self, node, child_tables):
        from auron_tpu.ir.schema import to_arrow_schema
        return pa.Table.from_pylist(node.attrs.get("rows", []),
                                    schema=to_arrow_schema(node.output))


def local_table(rows, schema):
    return ForeignNode("LocalTableScanExec", output=schema,
                       attrs={"rows": rows})


def exchange(child, key, n=4, mode="hash"):
    part = {"mode": mode, "num_partitions": n}
    if mode == "hash":
        part["expressions"] = [fcol(key, I64)]
    return ForeignNode("ShuffleExchangeExec", children=(child,),
                       output=child.output,
                       attrs={"partitioning": part})


def shj(left, right, join_type="Inner", build_side="right",
        lkey="k", rkey="k2"):
    return ForeignNode(
        "ShuffledHashJoinExec", children=(left, right),
        output=left.output.concat(right.output),
        attrs={"left_keys": [fcol(lkey, I64)],
               "right_keys": [fcol(rkey, I64)],
               "join_type": join_type, "build_side": build_side})


def two_phase_agg(src, n_parts=8):
    from auron_tpu.frontend import fcall
    from auron_tpu.frontend.foreign import ForeignExpr
    aggs = [ForeignExpr("AggregateExpression",
                        children=(fcall("Sum", fcol("v", F64),
                                        dtype=F64),))]
    partial = ForeignNode(
        "HashAggregateExec", children=(src,),
        output=Schema((Field("k", I64), Field("s#sum", F64))),
        attrs={"grouping": [fcol("k", I64)], "aggs": aggs,
               "agg_names": ["s"], "mode": "partial"})
    ex = ForeignNode(
        "ShuffleExchangeExec", children=(partial,),
        output=partial.output,
        attrs={"partitioning": {"mode": "hash",
                                "num_partitions": n_parts,
                                "expressions": [fcol("k", I64)]}})
    return ForeignNode(
        "HashAggregateExec", children=(ex,),
        output=Schema((Field("k", I64), Field("s", F64))),
        attrs={"grouping": [fcol("k", I64)], "aggs": aggs,
               "agg_names": ["s"], "mode": "final"})


def run(plan, overlay):
    with config.conf.scoped(overlay):
        return AuronSession(foreign_engine=ToyEngine()).execute(plan)


def canon(t: pa.Table):
    return sorted(map(tuple, (r.values() for r in t.to_pylist())))


def ordered(t: pa.Table):
    return list(map(tuple, (r.values() for r in t.to_pylist())))


def sales_rows(n, keys=13):
    return [{"k": i % keys, "v": float(i)} for i in range(n)]


def dim_rows(n):
    return [{"k2": i, "w": float(i * 10)} for i in range(n)]


def kinds(res):
    return [d["kind"] for d in res.aqe_decisions]


# ---------------------------------------------------------------------------
# broadcast-vs-shuffle conversion
# ---------------------------------------------------------------------------

def test_broadcast_conversion_fires_and_results_identical():
    plan = shj(exchange(local_table(sales_rows(600), SALES), "k"),
               exchange(local_table(dim_rows(13), DIM), "k2"))
    off = run(plan, SERIAL)
    b0 = counters.get("adaptive_broadcast")
    on = run(plan, {**AQE, "auron.adaptive.coalesce.enable": False,
                    "auron.adaptive.skew.enable": False})
    assert canon(off.table) == canon(on.table)
    assert off.table.num_rows == 600
    assert "broadcast" in kinds(on)
    assert counters.get("adaptive_broadcast") == b0 + 1
    d = next(d for d in on.aqe_decisions if d["kind"] == "broadcast")
    assert d["side"] == "right" and d["join_type"] == "inner"
    # the audit trail rides EXPLAIN ANALYZE in both render modes
    assert "aqe: broadcast" in on.explain_analyze(normalize=True)


def test_broadcast_respects_threshold():
    plan = shj(exchange(local_table(sales_rows(600), SALES), "k"),
               exchange(local_table(dim_rows(13), DIM), "k2"))
    on = run(plan, {**AQE, "auron.adaptive.broadcast.threshold.bytes": 1,
                    "auron.adaptive.coalesce.enable": False,
                    "auron.adaptive.skew.enable": False})
    assert "broadcast" not in kinds(on)


@pytest.mark.parametrize("join_type,build_side,expect", [
    ("Inner", "right", True),
    ("LeftOuter", "right", True),     # probe side emits unmatched: safe
    ("RightOuter", "right", False),   # build side emits unmatched: unsafe
    ("LeftSemi", "right", True),
    ("FullOuter", "right", False),
])
def test_broadcast_join_type_legality(join_type, build_side, expect):
    plan = shj(exchange(local_table(sales_rows(300, keys=16), SALES),
                        "k"),
               exchange(local_table(dim_rows(12), DIM), "k2"),
               join_type=join_type, build_side=build_side)
    off = run(plan, SERIAL)
    on = run(plan, {**AQE, "auron.adaptive.coalesce.enable": False,
                    "auron.adaptive.skew.enable": False})
    assert canon(off.table) == canon(on.table)
    assert ("broadcast" in kinds(on)) == expect


def test_broadcast_removes_partitioned_fetch():
    """The converted exchange registers ONE collected block list (the
    broadcast form) — the per-reduce-partition shuffle_read metrics of
    the build side disappear while the probe side keeps its own."""
    plan = shj(exchange(local_table(sales_rows(400), SALES), "k"),
               exchange(local_table(dim_rows(13), DIM), "k2"))
    overlay = {**AQE, "auron.adaptive.coalesce.enable": False,
               "auron.adaptive.skew.enable": False}
    off = run(plan, SERIAL)
    on = run(plan, overlay)
    assert "broadcast" in kinds(on)

    def n_shuffle_readers(res):
        out = 0
        for tree in res.metrics:
            for node in _walk_metric(tree):
                if node.name.startswith("IpcReaderExec") and \
                        node.values.get("shuffle_read_bytes"):
                    out += 1
        return out

    # off: both sides fetch partitioned (4 probe + 4 build reader
    # nodes carry shuffle_read_bytes); on: only the probe side does
    assert n_shuffle_readers(on) < n_shuffle_readers(off)


def _walk_metric(node):
    node._settle()
    yield node
    for c in node.children:
        yield from _walk_metric(c)


# ---------------------------------------------------------------------------
# partition coalescing
# ---------------------------------------------------------------------------

@pytest.mark.slow   # PR 18 tier-1 re-split (8.6s; coalesce parity
# also rides test_corpus_equivalence_aqe_on_off)
def test_coalesce_reduces_reduce_tasks_identically():
    plan = two_phase_agg(local_table(sales_rows(2000, keys=40), SALES),
                         n_parts=8)
    off = run(plan, SERIAL)
    c0 = counters.get("adaptive_coalesce")
    on = run(plan, {**AQE, "auron.adaptive.broadcast.enable": False,
                    "auron.adaptive.skew.enable": False})
    assert canon(off.table) == canon(on.table)
    assert "coalesce" in kinds(on)
    assert counters.get("adaptive_coalesce") == c0 + 1
    d = next(d for d in on.aqe_decisions if d["kind"] == "coalesce")
    assert d["to_partitions"] < d["from_partitions"] == 8

    def reduce_tasks(res):
        # metric groups whose root is the final AggExec: task count ==
        # reduce partition count
        from auron_tpu.runtime.explain_analyze import merge_metric_trees
        return sum(n for t, n in merge_metric_trees(res.metrics)
                   if t.name.startswith("AggExec"))

    assert reduce_tasks(on) < reduce_tasks(off) == 8


def test_coalesce_keeps_co_partitioned_join_sides_aligned():
    """Both sides of a shuffled join get the SAME grouping (computed
    from combined bytes) or key alignment would break."""
    plan = shj(exchange(local_table(sales_rows(2000, keys=50), SALES),
                        "k", n=8),
               exchange(local_table([{"k2": i, "w": float(i)}
                                     for i in range(800)], DIM),
                        "k2", n=8))
    off = run(plan, SERIAL)
    on = run(plan, {**AQE, "auron.adaptive.broadcast.enable": False,
                    "auron.adaptive.skew.enable": False})
    assert canon(off.table) == canon(on.table)
    coal = [d for d in on.aqe_decisions if d["kind"] == "coalesce"]
    assert len(coal) == 2
    assert coal[0]["to_partitions"] == coal[1]["to_partitions"]


def test_coalesce_respects_target_bytes():
    plan = two_phase_agg(local_table(sales_rows(2000, keys=40), SALES),
                         n_parts=8)
    on = run(plan, {**AQE, "auron.adaptive.broadcast.enable": False,
                    "auron.adaptive.skew.enable": False,
                    "auron.adaptive.target.partition.bytes": 1})
    assert "coalesce" not in kinds(on)   # every partition overflows 1B


# ---------------------------------------------------------------------------
# skew splitting
# ---------------------------------------------------------------------------

def _skewed_plan(rows_per_chunk=4000, chunks=4):
    parts = [local_table(
        [{"k": 7 if i % 4 else (i % 97), "v": float(i)}
         for i in range(c * rows_per_chunk,
                        (c + 1) * rows_per_chunk)], SALES)
        for c in range(chunks)]
    union = ForeignNode("UnionExec", children=tuple(parts), output=SALES)
    ex = exchange(union, "k", n=4)
    return ForeignNode(
        "ProjectExec", children=(ex,), output=SALES,
        attrs={"project_list": [fcol("k", I64), fcol("v", F64)]})


SKEW_ON = {**AQE, "auron.adaptive.broadcast.enable": False,
           "auron.adaptive.coalesce.enable": False,
           "auron.adaptive.skew.factor": 2.0,
           "auron.adaptive.skew.min.partition.bytes": 1024,
           "auron.adaptive.target.partition.bytes": 1 << 18}


def test_skew_split_fans_out_order_preserving():
    plan = _skewed_plan()
    off = run(plan, SERIAL)
    s0 = counters.get("adaptive_skew_split")
    on = run(plan, SKEW_ON)
    # order-preserving concat: the split parts are adjacent partitions,
    # so even the emitted ROW ORDER matches the unsplit run
    assert ordered(off.table) == ordered(on.table)
    assert "skew_split" in kinds(on)
    assert counters.get("adaptive_skew_split") == s0 + 1
    from auron_tpu.runtime.explain_analyze import merge_metric_trees
    tasks_on = sum(n for t, n in merge_metric_trees(on.metrics)
                   if t.name.startswith("ProjectExec"))
    tasks_off = sum(n for t, n in merge_metric_trees(off.metrics)
                    if t.name.startswith("ProjectExec"))
    assert tasks_on > tasks_off == 4


def test_skew_split_declined_for_non_row_local_consumer():
    """An agg above the skewed exchange reasons over whole hash
    partitions — the split must decline (and say why)."""
    parts = [local_table(
        [{"k": 7 if i % 4 else (i % 97), "v": float(i)}
         for i in range(c * 4000, (c + 1) * 4000)], SALES)
        for c in range(4)]
    union = ForeignNode("UnionExec", children=tuple(parts), output=SALES)
    from auron_tpu.frontend import fcall
    from auron_tpu.frontend.foreign import ForeignExpr
    aggs = [ForeignExpr("AggregateExpression",
                        children=(fcall("Sum", fcol("v", F64),
                                        dtype=F64),))]
    final = ForeignNode(
        "HashAggregateExec", children=(exchange(union, "k", n=4),),
        output=Schema((Field("k", I64), Field("s", F64))),
        attrs={"grouping": [fcol("k", I64)], "aggs": aggs,
               "agg_names": ["s"], "mode": "single"})
    off = run(final, SERIAL)
    on = run(final, SKEW_ON)
    assert canon(off.table) == canon(on.table)
    assert "skew_split" not in kinds(on)
    declined = [d for d in on.aqe_decisions if d["kind"] == "declined"]
    assert any("skew" in d["reason"] for d in declined)


def test_split_skewed_partition_rearms_v2_headers():
    """Chunks after the first open with a header-less v2 frame; the
    splitter re-arms the stream header so every chunk decodes."""
    import io

    from auron_tpu.columnar import serde
    from auron_tpu.columnar.batch import Batch
    table = pa.table({"x": list(range(64))})
    from auron_tpu.ir.schema import from_arrow_schema
    schema = from_arrow_schema(table.schema)
    b = Batch.from_arrow(table.to_batches()[0], schema=schema)
    header = serde.encode_stream_header(schema)
    frame = serde.encode_batch_v2(b)
    # one partition stream: header+frame, then three frame-only pushes
    blocks = [[header + frame, frame, frame, frame]]
    out = adaptive.split_skewed_partition(blocks, 0, 4)
    assert len(out) == 4
    rows = 0
    for chunk in out:
        got = list(serde.read_batches(
            io.BytesIO(b"".join(bytes(x) for x in chunk))))
        rows += sum(g.num_rows for g in got)
    assert rows == 64 * 4


def test_merge_partition_groups_concatenates_in_order():
    blocks = [[b"a"], [b"b", b"c"], [], [b"d"]]
    merged = adaptive.merge_partition_groups(blocks, [[0, 1], [2, 3]])
    assert merged == [[b"a", b"b", b"c"], [b"d"]]


# ---------------------------------------------------------------------------
# verifier coverage for rewritten plans
# ---------------------------------------------------------------------------

def test_rewritten_plans_are_verifier_clean():
    """Every decision the session applied came from a rewrite that the
    full analyzer battery (including the adaptive pass) accepted — and
    the executed plan was verified AGAIN by the verify-before-execute
    gate (on under pytest), so a surviving query IS the assertion.
    Belt and braces: replan manually and analyze the result."""
    from auron_tpu.analysis import analyze
    from auron_tpu.frontend import converters, strategy
    plan = shj(exchange(local_table(sales_rows(200), SALES), "k"),
               exchange(local_table(dim_rows(13), DIM), "k2"))
    tags = strategy.apply(plan)
    ctx = converters.ConvertContext()
    converted = converters.convert_recursively(plan, tags, ctx)
    rid = next(iter(ctx.exchanges))
    rids = list(ctx.exchanges)
    stats = {rids[1]: adaptive.ExchangeStats(
        rid=rids[1], partition_bytes=[100] * 4,
        partition_rows=[3] * 4)}
    with config.conf.scoped(AQE):
        new_plan, decisions, actions = adaptive.replan(
            converted, ctx, stats)
    assert [d.kind for d in decisions] == ["broadcast"]
    assert rids[1] in actions
    res = analyze(new_plan)
    assert res.ok, [str(d) for d in res.diagnostics]
    assert any(n.kind == "broadcast_join" for n in P.walk(new_plan))
    assert rid  # the probe exchange survives untouched


def test_adaptive_pass_rejects_mismatched_cache_id():
    from auron_tpu.analysis import analyze
    reader = P.IpcReader(schema=DIM, resource_id="x")
    bhm = P.BroadcastJoinBuildHashMap(
        child=reader, keys=(fcol_expr("k2"),), cache_id="a")
    join = P.BroadcastJoin(
        left=P.IpcReader(schema=SALES, resource_id="y"), right=bhm,
        on=P.JoinOn(left_keys=(fcol_expr("k"),),
                    right_keys=(fcol_expr("k2"),)),
        join_type="inner", broadcast_side="right",
        cached_build_hash_map_id="DIFFERENT")
    res = analyze(join)
    assert any(d.pass_id == "adaptive" and d.severity == "error"
               for d in res.diagnostics)


def test_adaptive_pass_rejects_build_side_outer_broadcast():
    from auron_tpu.analysis import analyze
    bhm = P.BroadcastJoinBuildHashMap(
        child=P.IpcReader(schema=DIM, resource_id="x"),
        keys=(fcol_expr("k2"),), cache_id="a")
    join = P.BroadcastJoin(
        left=P.IpcReader(schema=SALES, resource_id="y"), right=bhm,
        on=P.JoinOn(left_keys=(fcol_expr("k"),),
                    right_keys=(fcol_expr("k2"),)),
        join_type="right", broadcast_side="right",
        cached_build_hash_map_id="a")
    res = analyze(join)
    assert any(d.pass_id == "adaptive" and d.severity == "error"
               for d in res.diagnostics)


def fcol_expr(name):
    from auron_tpu.ir import expr as E
    return E.Column(name=name)


# ---------------------------------------------------------------------------
# observed exchange stats are surfaced (AQE on OR off)
# ---------------------------------------------------------------------------

def test_exchange_stats_surfaced_without_aqe():
    from auron_tpu.runtime import tracing
    plan = two_phase_agg(local_table(sales_rows(500), SALES), n_parts=4)
    res = run(plan, SERIAL)
    assert len(res.exchange_stats) == 1
    st = res.exchange_stats[0]
    assert st["partitions"] == 4 and st["rows_out"] > 0
    assert st["bytes_out"] == sum(st["partition_bytes"]) > 0
    # the query-history record carries them (-> /queries/<id> JSON)
    rec = tracing.find_query(res.query_id)
    assert rec is not None and rec.exchange_stats == res.exchange_stats
    assert rec.aqe_decisions is None
    # and the metric tree grew an ExchangeStats marker group
    assert any(t.name.startswith("ExchangeStats[")
               for t in res.metrics)


# ---------------------------------------------------------------------------
# unified cost model
# ---------------------------------------------------------------------------

def test_cost_model_keeps_live_history():
    m = adaptive.CostModel()
    # per-(signature, exchange) history
    st = adaptive.ExchangeStats(rid="shuffle:u:3",
                                partition_bytes=[10, 20],
                                partition_rows=[1, 2])
    m.record_exchange("sigA", st)
    assert m.expected_exchange_bytes("sigA", "x3") == 30
    assert m.expected_exchange_bytes("sigA", "x9") is None
    big = adaptive.ExchangeStats(rid="shuffle:u:3",
                                 partition_bytes=[500, 20],
                                 partition_rows=[1, 2])
    m.record_exchange("sigA", big)
    assert m.expected_exchange_bytes("sigA", "x3") == 520


def test_filter_adjacency_is_cost_chosen():
    from auron_tpu.ir import expr as E
    m = adaptive.unified_cost_model()
    pred = E.BinaryExpr(left=E.Column(name="k"), op=">",
                        right=E.Literal(dtype=I64, value=3))
    assert m.filter_adjacency_pays((pred,), SALES)
    # a long conjunction's re-evaluation outweighs the fused saving
    assert not m.filter_adjacency_pays(tuple([pred] * 16), SALES)


def test_conversion_emits_adjacent_filter_when_enabled(tmp_path):
    from auron_tpu.frontend import converters, strategy
    from auron_tpu.frontend.foreign import ForeignExpr
    cat = datagen.generate(str(tmp_path / "adj"), sf=0.002,
                           fact_chunks=2)
    qf = cat.field("store_sales", "ss_quantity")
    cond = ForeignExpr("GreaterThan", children=(
        fcol("ss_quantity", qf.dtype), flit(2, qf.dtype)))
    scan = cat.scan("store_sales", ["ss_item_sk", "ss_quantity"],
                    pushed_filters=[cond])

    def convert(overlay):
        with config.conf.scoped(overlay):
            tags = strategy.apply(scan)
            ctx = converters.ConvertContext()
            return converters.convert_recursively(scan, tags, ctx)

    plain = convert(SERIAL)
    assert plain.kind == "parquet_scan"
    adj = convert({**SERIAL,
                   "auron.adaptive.fuse.adjacency.enable": True})
    # the pushed filter now ALSO stands adjacent above the scan, where
    # the fuser can see it — the scan predicate still prunes IO
    assert adj.kind == "filter" and adj.child.kind == "parquet_scan"
    assert adj.child.predicate is not None


# ---------------------------------------------------------------------------
# stage-boundary admission re-forecast
# ---------------------------------------------------------------------------

def test_reforecast_releases_reservation_at_stage_boundary():
    """The acceptance unit test: a query forecast fat (history says
    256MB) turns out light — the admission ledger DROPS at the stage
    boundary, mid-query, not at completion."""
    from auron_tpu.serving import AdmissionController, QueryScheduler
    from auron_tpu.serving.forecast import plan_signature

    samples = []

    class Recording(AdmissionController):
        def reforecast(self, qid, live, age_s=0.0):
            out = super().reforecast(qid, live, age_s)
            samples.append({"target": out,
                            "held": self.held_bytes()})
            return out

    admission = Recording(budget_fn=lambda: 1 << 30)
    plan = two_phase_agg(local_table(sales_rows(800), SALES), n_parts=4)
    sig = plan_signature(plan)
    admission.observe(sig, 256 << 20)     # history: this shape is FAT
    sched = QueryScheduler(admission=admission)
    try:
        qid = sched.submit(plan, conf={
            **AQE,
            "auron.admission.reforecast.min.age.seconds": 0.0})
        assert sched.wait(qid, timeout=60)
        sub = sched.get(qid)
        assert sub.state == "succeeded"
        initial = sub.forecast_bytes
        assert initial >= 256 << 20
        assert samples, "stage boundary never re-forecast"
        # the ledger dropped while the query was still RUNNING
        assert samples[-1]["target"] is not None
        assert samples[-1]["held"] < initial
        assert admission.events["reforecast"] >= 1
    finally:
        sched.shutdown(wait=True)


def test_reforecast_hook_cleared_after_query():
    from auron_tpu.runtime.adaptive import (
        _REFORECAST_HOOKS, clear_reforecast_hook, set_reforecast_hook,
    )
    set_reforecast_hook("qx", lambda est, age: None)
    assert "qx" in _REFORECAST_HOOKS
    clear_reforecast_hook("qx")
    assert "qx" not in _REFORECAST_HOOKS


# ---------------------------------------------------------------------------
# exchange codec policy
# ---------------------------------------------------------------------------

def test_exchange_codec_policy_split_by_transport():
    from auron_tpu.columnar import serde
    assert serde.exchange_codec("local") == "none"
    assert serde.exchange_codec("remote") is None   # -> default codec
    with config.conf.scoped({"auron.shuffle.codec.local": "",
                             "auron.shuffle.codec.remote": "zlib"}):
        assert serde.exchange_codec("local") is None
        assert serde.exchange_codec("remote") == "zlib"


def test_inprocess_exchange_frames_are_uncompressed():
    """The in-process service stores what the writer pushed: with the
    default local policy the v2 frame codec id must be `none` (the
    compress-only-to-decompress round trip is gone)."""
    from auron_tpu.ops.shuffle.writer import InProcessShuffleService
    svc = InProcessShuffleService()
    session_plan = two_phase_agg(local_table(sales_rows(400), SALES),
                                 n_parts=2)
    with config.conf.scoped(SERIAL):
        session = AuronSession(foreign_engine=ToyEngine(),
                               shuffle_service=svc)
        # keep blocks around for inspection: clear() runs at execute
        # end, so snapshot via a wrapper
        seen = []
        orig = svc.reduce_blocks

        def spy(shuffle_id, reduce_pid):
            out = orig(shuffle_id, reduce_pid)
            seen.extend(out)
            return out

        svc.reduce_blocks = spy
        session.execute(session_plan)
    assert seen
    import struct
    for block in seen:
        buf = bytes(block)
        # skip the v2 stream header if present
        if buf[:4] == b"\xff\xff\xff\xff":
            (ln,) = struct.unpack_from("<I", buf, 5)
            buf = buf[9 + ln:]
        if not buf:
            continue
        codec_id = buf[4] & 0x7F
        assert codec_id == 0, "expected codec none on local transport"


# ---------------------------------------------------------------------------
# equivalence property: corpus queries, AQE on == off (serial)
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def corpus_catalog(tmp_path_factory):
    return datagen.generate(str(tmp_path_factory.mktemp("aqe_tpcds")),
                            sf=0.002, fact_chunks=3)


# tier-1 kept two cheap exemplars; q42 moved to -m slow at PR 16 and
# q03 (17.8s) follows at PR 18 (tier-1 re-split) — the forced-decision
# unit tests above stay fast, and corpus-level AQE equivalence rides
# the nightly sweep plus the tools/aqe_check.sh CI gate
CORPUS_FAST = [pytest.param("q03", marks=pytest.mark.slow),
               pytest.param("q42", marks=pytest.mark.slow)]
AQE_FORCED = {
    **AQE,
    # force decisions to actually fire on the tiny corpus
    "auron.adaptive.target.partition.bytes": 1 << 20,
    "auron.force.shuffled.hash.join": True,
}


def _run_corpus(name, cat, overlay):
    plan = queries.build(name, cat)
    with config.conf.scoped(overlay):
        session = AuronSession(foreign_engine=PyArrowEngine())
        res = session.execute(plan)
    return plan, res


@pytest.mark.parametrize("name", CORPUS_FAST)
def test_corpus_equivalence_aqe_on_off(corpus_catalog, name):
    plan, off = _run_corpus(name, corpus_catalog,
                            {**SERIAL,
                             "auron.force.shuffled.hash.join": True})
    _, on = _run_corpus(name, corpus_catalog, AQE_FORCED)
    err = compare.compare_tables(on.table, off.table,
                                 ordered=compare.plan_is_ordered(plan))
    assert err is None, f"{name}: {err}"
    assert on.aqe_decisions, f"{name}: no adaptive decision fired"


@pytest.mark.slow
def test_corpus_equivalence_full_sweep(corpus_catalog):
    """Nightly: every corpus query value-identical with AQE on vs off
    (tools/aqe_check.sh runs the skew/coalesce-targeted subset)."""
    failures = []
    fired = 0
    for name in queries.names():
        try:
            plan, off = _run_corpus(
                name, corpus_catalog,
                {**SERIAL, "auron.force.shuffled.hash.join": True})
            _, on = _run_corpus(name, corpus_catalog, AQE_FORCED)
        except Exception as e:  # noqa: BLE001 - collected for report
            failures.append(f"{name}: {type(e).__name__}: {e}")
            continue
        err = compare.compare_tables(
            on.table, off.table, ordered=compare.plan_is_ordered(plan))
        if err is not None:
            failures.append(f"{name}: {err}")
        fired += bool(on.aqe_decisions)
        import jax
        jax.clear_caches()
    assert not failures, failures[:5]
    assert fired > len(queries.names()) // 2


@pytest.mark.slow
def test_fleet_equivalence_aqe_on_off(corpus_catalog):
    """The fleet variant: workers run serial sessions, so the per-query
    conf overlay carries AQE across the dispatch boundary."""
    from auron_tpu.serving import register_catalog
    from auron_tpu.serving.executor_endpoint import (
        ExecutorServer, ProcessExecutor,
    )
    from auron_tpu.serving.fleet import FleetManager
    register_catalog(0.002, corpus_catalog)
    plan = queries.build("q42", corpus_catalog)
    with config.conf.scoped(SERIAL):
        solo = AuronSession(foreign_engine=PyArrowEngine()).execute(plan)
    srv = ExecutorServer(executor_id="e1").start()
    fleet = None
    try:
        ep = ProcessExecutor("e1", *srv.address)
        fleet = FleetManager(endpoints=[ep])
        qid = fleet.submit(plan, conf=dict(AQE_FORCED))
        assert fleet.wait(qid, timeout=120), fleet.status(qid)
        st = fleet.status(qid)
        assert st["state"] == "succeeded", st
        table = fleet.result(qid)
        err = compare.compare_tables(
            table, solo.table, ordered=compare.plan_is_ordered(plan))
        assert err is None, err
    finally:
        if fleet is not None:
            fleet.shutdown(wait=True)
        srv.stop()


@pytest.mark.slow
def test_second_run_compiles_zero_with_aqe():
    """Coalesced/broadcast shapes must not retrace-storm: a repeat of
    the same query under AQE compiles NOTHING new (reduce programs pad
    to capacity, so coalesced shapes reuse cached programs)."""
    plan = shj(exchange(local_table(sales_rows(1500, keys=30), SALES),
                        "k", n=6),
               exchange(local_table(dim_rows(30), DIM), "k2", n=6))
    overlay = {**AQE}
    run(plan, overlay)           # warm: traces everything once

    def compile_total():
        from auron_tpu.runtime import jitcheck
        return sum(jitcheck.compile_counts().values())

    before = compile_total()
    res = run(plan, overlay)
    assert res.table.num_rows == 1500
    assert compile_total() == before, \
        "AQE repeat run recompiled a program (shape churn)"


@pytest.mark.slow
def test_tools_aqe_check_script():
    import os
    import subprocess
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        ["bash", os.path.join(root, "tools", "aqe_check.sh")],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout[-4000:]}\nstderr:\n{proc.stderr[-4000:]}"

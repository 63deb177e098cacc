"""Every second of a stage-path execute under a name (PR 26): a named
scope per plan operator inside the stage program, leaf spans under
`task.execute` / `spmd.shard` / `spmd.gather`, the program's spans on the
profiler's clock, and the `python -m auron_tpu.trace device` reduction."""

import glob
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

import jax

from auron_tpu import trace as trace_cli
from auron_tpu.config import conf
from auron_tpu.frontend.converters import BroadcastJob, ShuffleJob
from auron_tpu.ir import plan as P
from auron_tpu.ir.expr import AggExpr, SortExpr, col, lit
from auron_tpu.ir import expr as E
from auron_tpu.ir.plan import FileGroup, JoinOn
from auron_tpu.ir.schema import DataType, Field, Schema
from auron_tpu.parallel import stage as S
from auron_tpu.parallel.mesh import data_mesh
from auron_tpu.runtime import tracing
from stage_spy import spied_program

I64 = DataType.int64()
F64 = DataType.float64()

# the leaves of the issue's table and the span each lies under
LEAVES = {
    "scan.decode": "task.execute", "scan.to_device": "task.execute",
    "task.to_host": "task.execute", "spmd.tail": "spmd.launch",
    "shard.pad": "spmd.shard", "shard.put": "spmd.shard",
    "spmd.wait": "spmd.gather", "spmd.fetch": "spmd.gather",
}
# operators that bind or pass a table on and trace no operation
PASS_THROUGH = ("parquet_scan", "broadcast_join_build_hash_map")


class _Ctx:
    def __init__(self):
        self.exchanges = {}
        self.broadcasts = {}


@pytest.fixture(scope="module")
def files(tmp_path_factory):
    """A fact table in three files and two dimension tables."""
    d = tmp_path_factory.mktemp("stage_tracing")
    rng = np.random.default_rng(7)
    fact = []
    for i in range(3):
        path = str(d / f"fact{i}.parquet")
        pq.write_table(pa.table({
            "k1": rng.integers(0, 16, 400).astype(np.int64),
            "k2": rng.integers(0, 8, 400).astype(np.int64),
            "amount": rng.normal(10, 3, 400)}), path)
        fact.append(path)
    dims = {}
    for name, n in (("d1", 16), ("d2", 8)):
        dims[name] = str(d / f"{name}.parquet")
        pq.write_table(pa.table({
            f"{name}_key": np.arange(n, dtype=np.int64),
            f"{name}_grp": (np.arange(n, dtype=np.int64) % 4)}),
            dims[name])
    return {"fact": fact, **dims}


def build_plan(files, uid):
    """scan -> filter -> broadcast join x2 -> partial agg -> hash exchange
    -> final agg -> sort (the driver tail); resource ids as one conversion
    (`uid`) would mint them."""
    ctx = _Ctx()
    fact = P.ParquetScan(
        schema=Schema((Field("k1", I64), Field("k2", I64),
                       Field("amount", F64))),
        file_groups=tuple(FileGroup(paths=(p,)) for p in files["fact"]))
    node = P.Filter(child=fact, predicates=(
        E.BinaryExpr(op=">", left=col("amount"), right=lit(0.0)),))
    for name, key in (("d1", "k1"), ("d2", "k2")):
        dim = P.ParquetScan(
            schema=Schema((Field(f"{name}_key", I64),
                           Field(f"{name}_grp", I64))),
            file_groups=(FileGroup(paths=(files[name],)),))
        rid = f"bc:{uid}:{name}"
        ctx.broadcasts[rid] = BroadcastJob(rid=rid, child=dim, schema=None)
        node = P.BroadcastJoin(
            left=node, right=P.IpcReader(schema=None, resource_id=rid),
            on=JoinOn(left_keys=(col(key),),
                      right_keys=(col(f"{name}_key"),)),
            join_type="inner", broadcast_side="right")
    agg = dict(grouping=(col("d1_grp"),), grouping_names=("d1_grp",),
               aggs=(AggExpr(fn="sum", children=(col("amount"),),
                             return_type=F64),), agg_names=("s",))
    rid = f"ex:{uid}"
    ctx.exchanges[rid] = ShuffleJob(
        rid=rid, child=P.Agg(child=node, exec_mode="partial", **agg),
        partitioning=P.Partitioning(mode="hash", num_partitions=8,
                                    expressions=(col("d1_grp"),)),
        schema=None)
    final = P.Agg(child=P.IpcReader(schema=None, resource_id=rid),
                  exec_mode="final", **agg)
    return P.Sort(child=final,
                  sort_exprs=(SortExpr(child=col("d1_grp")),)), ctx


def test_operator_scopes_in_the_lowered_stage_program(files):
    mesh = data_mesh(8)
    p1, c1 = build_plan(files, "aaaa1111")
    p2, c2 = build_plan(files, "bbbb2222")
    before = set(S._PROGRAM_CACHE)
    got1 = S.execute_plan_spmd(p1, c1, mesh, {})
    [key] = set(S._PROGRAM_CACHE) - before
    shard, *boxes = S._PROGRAM_CACHE[key]
    calls = []

    def spy(inputs):
        calls.append(inputs)
        return shard(inputs)

    S._PROGRAM_CACHE[key] = (spy, *boxes)
    try:
        got2 = S.execute_plan_spmd(p2, c2, mesh, {})
    finally:
        S._PROGRAM_CACHE[key] = (shard, *boxes)
    # the second conversion ran the first one's program
    assert len(calls) == 1 and set(S._PROGRAM_CACHE) - before == {key}
    assert got1.to_pylist() == got2.to_pylist()

    text = shard.__wrapped__.lower(calls[0]).as_text(debug_info=True)
    explained = S.explain_stage(p1, c1)
    assert explained == S.explain_stage(p2, c2)
    assert explained.splitlines()[0].startswith("sort (driver tail")
    labels = [line.split()[0] for line in explained.splitlines()[1:]]
    assert len(labels) == len(set(labels)) == 11
    kinds = [lb.split("#")[0] for lb in labels]
    assert kinds.count("broadcast_join") == 2 and kinds.count("agg") == 2
    for label in labels:
        if label.startswith(PASS_THROUGH):
            continue
        # the root's path starts the scope string, a child's follows "/"
        assert f'"{label}/' in text or f"/{label}/" in text, label
        if label.startswith("broadcast_join#"):
            assert f"{label}/build/" in text and f"{label}/probe/" in text
        if label.startswith("agg#"):
            assert f"{label}/group/" in text and f"{label}/reduce/" in text
    # the collectives lie under their boundary's label
    assert "/exchange/" in text and "/broadcast/" in text
    # an exchange's send buffers, its transfer and its counts apart
    for sub in ("scatter", "all_to_all", "count"):
        assert f"/exchange/{sub}/" in text, sub
    assert "/broadcast/count/" in text
    assert '"epilogue/' in text
    # nesting reads back to the tree: a child's scope inside its parent's
    join_labels = [lb for lb in labels if lb.startswith("broadcast_join#")]
    assert f"{join_labels[0]}/{join_labels[1]}/probe/" in text


def test_compact_scope_under_an_aggregate_that_chose(files):
    """On one device the 1,200 fact rows lie in 2,048 slots: with the
    capacity hint scoped down to 1,024 the partial aggregate chooses its
    input, and both what it may do to it — bring the live rows to the
    front of 1,024 slots, or cut its output to them — are filed under
    `agg#…/compact`, inside the conditional; the final aggregate, whose
    input is those 1,024 slots, has neither."""
    plan, ctx = build_plan(files, "cccc3333")
    with conf.scoped({"auron.spmd.agg.capacity.hint": 1024}):
        program, inputs = spied_program(plan, ctx, data_mesh(1), {})
        text = program.lower(inputs).as_text(debug_info=True)
    explained = S.explain_stage(plan, ctx)
    partial, final = [
        line.split()[0] for line in explained.splitlines()
        if "mode=partial" in line or "mode=final" in line][::-1]
    # the running count, the scatter of row numbers and the columns'
    # gathers on the compact side (the switch's sides ascend by width:
    # branch 0); the cut on the full side, the last
    for branch, op in ((0, "jit(cumsum)"), (0, "scatter"),
                       (0, "jit(_take)"), (1, "slice")):
        assert f"{partial}/cond/branch_{branch}_fun/compact/{op}" in text, op
    assert f"{final}/cond/" not in text and f"{final}/compact/" not in text
    # the body's scopes on both sides of the choice
    for branch in ("branch_0_fun", "branch_1_fun"):
        for scope in ("group", "reduce"):
            assert f"{partial}/cond/{branch}/{scope}/" in text, (branch,
                                                                 scope)


def _traced_execute(files, uid, scope):
    plan, ctx = build_plan(files, uid)
    rec = tracing.TraceRecorder(uid, max_events=10_000)
    S.clear_source_caches()
    with conf.scoped(scope), tracing.trace_scope(recorder=rec,
                                                 query_id=uid):
        with tracing.span("query", cat="query", query_id=uid):
            table = S.execute_plan_spmd(plan, ctx, data_mesh(8), {})
    return table, rec


def test_leaf_spans_nest_under_their_parents(files):
    table, rec = _traced_execute(
        files, "leaves", {"auron.task.parallelism": 4})
    assert table.num_rows == 4
    spans = [s for s in rec.snapshot() if s.dur_ns >= 0]
    by_id = {s.id: s for s in spans}
    assert len(by_id) == len(spans) and 0 not in by_id
    names = {s.name for s in spans}
    assert set(LEAVES) <= names, set(LEAVES) - names
    for s in spans:
        if s.name not in LEAVES:
            continue
        parent = by_id[s.parent]
        assert parent.name == LEAVES[s.name], (s.name, parent.name)
        assert parent.t0_ns <= s.t0_ns and \
            s.t0_ns + s.dur_ns <= parent.t0_ns + parent.dur_ns
    # children of one thread sum to no more than their parent
    for parent in spans:
        per_thread = {}
        for s in spans:
            if s.parent == parent.id:
                per_thread[s.tid] = per_thread.get(s.tid, 0) + s.dur_ns
        assert all(v <= parent.dur_ns for v in per_thread.values()), \
            parent.name
    # a scan task's first span finds the span that submitted it
    ingest = next(s for s in spans if s.name == "spmd.ingest")
    tasks = [s for s in spans if s.name == "task.execute"
             and s.parent == ingest.id]
    assert len(tasks) == 5            # three fact files, two dimensions
    decode = [s for s in spans if s.name == "scan.decode" and s.args]
    assert sum(s.args["rows"] for s in decode) == 1200 + 16 + 8
    assert all(s.args["bytes"] > 0 for s in decode)
    pads = [s for s in spans if s.name == "shard.pad"]
    assert sorted(s.args["rows"] for s in pads) == [8, 16, 1200]
    fetch = next(s for s in spans if s.name == "spmd.fetch")
    assert fetch.args["rows"] == 4 and fetch.args["bytes"] > 0
    # exported: the id and the parent of every span, and the unix anchor
    doc = rec.to_chrome_trace()
    assert tracing.validate_chrome_trace(doc) == []
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert all(e["args"]["id"] and "parent" in e["args"] for e in xs)
    assert doc["otherData"]["epoch_unix_ns"] == rec.epoch_unix_ns
    assert not any(s.name == "execute" for s in spans)


def test_tracing_off_records_and_allocates_nothing(files, tmp_path):
    plan, ctx = build_plan(files, "off")
    assert tracing.current_recorder() is None
    table = S.execute_plan_spmd(plan, ctx, data_mesh(8), {})
    assert table.num_rows == 4
    noop = tracing.span("scan.decode", cat="scan")
    assert noop is tracing.span("shard.put") and not noop.armed
    # through a session: the result carries no recorder
    from auron_tpu.frontend.session import AuronSession
    from auron_tpu.it import queries
    from auron_tpu.it.datagen import generate
    from auron_tpu.it.oracle import PyArrowEngine
    catalog = generate(str(tmp_path / "tpcds"), sf=0.002)
    session = AuronSession(foreign_engine=PyArrowEngine())
    res = session.execute(queries.build("q03", catalog))
    assert res.spmd and res.trace is None
    text = res.explain_analyze()
    assert "python -m auron_tpu.trace device" in text
    assert "broadcast_join#" in text and "(driver tail" in text
    assert text == res.explain_analyze()


def test_program_spans_on_the_profilers_clock(files, tmp_path):
    """Under a profile every program span is an annotation in the host
    plane, and `epoch_unix_ns + t0_ns` is its start on the profile's
    clock: this jax's profile counts nanoseconds from the
    `profile_start_time` (unix) of its "Task Environment" plane."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _table, rec = _traced_execute(files, "profiled", {})
    finally:
        jax.profiler.stop_trace()
    [path] = glob.glob(os.path.join(str(tmp_path), "plugins", "profile",
                                    "*", "*.xplane.pb"))
    data = ProfileData.from_file(path)
    start_unix = next(dict(p.stats)["profile_start_time"]
                      for p in data.planes if p.name == "Task Environment")
    recorded = [s for s in rec.snapshot() if s.dur_ns >= 0]
    wanted = {s.name for s in recorded}
    assert set(LEAVES) | {"query", "task.execute"} <= wanted
    # the enqueue: `spmd.compile` where this process runs the program first
    assert wanted & {"spmd.run", "spmd.compile"}
    found = {}
    for plane in data.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name in wanted:
                    found.setdefault(e.name, []).append(e)
    for name in wanted:
        assert len(found.get(name, ())) == \
            sum(s.name == name for s in recorded), name
    [query] = [s for s in recorded if s.name == "query"]
    [mark] = found["query"]
    assert dict(mark.stats)["query_id"] == "profiled"
    gap_ns = (rec.epoch_unix_ns + query.t0_ns) - (start_unix
                                                  + mark.start_ns)
    assert abs(gap_ns) < 5e6, gap_ns
    # the annotation encloses the span it carries
    assert mark.duration_ns >= query.dur_ns


def _eqns(jaxpr, inside=()):
    """Every equation of a jaxpr and of the jaxprs its equations hold,
    with the chain of (primitive name, branch index) it lies under."""
    for eqn in jaxpr.eqns:
        yield eqn, inside
        for name, value in eqn.params.items():
            subs = value if isinstance(value, (tuple, list)) else (value,)
            for i, sub in enumerate(subs):
                sub = getattr(sub, "jaxpr", sub)     # ClosedJaxpr -> Jaxpr
                if hasattr(sub, "eqns"):
                    yield from _eqns(
                        sub, inside + ((eqn.primitive.name, i),))


def test_one_conditional_a_join_and_no_loop_on_its_direct_side(files):
    """A query-7-shaped plan: four chained broadcast joins, each on one
    integer key of a small table.  Each join lowers to one conditional;
    its direct side (branch 1) is a scatter and gathers, no while loop;
    the search side (branch 0) keeps `searchsorted`'s."""
    ctx = _Ctx()
    node = P.ParquetScan(
        schema=Schema((Field("k1", I64), Field("k2", I64),
                       Field("amount", F64))),
        file_groups=tuple(FileGroup(paths=(p,)) for p in files["fact"]))
    for i, (name, key) in enumerate((("d1", "k1"), ("d2", "k2"),
                                     ("d1", "k1"), ("d2", "k2"))):
        dim = P.RenameColumns(
            child=P.ParquetScan(
                schema=Schema((Field(f"{name}_key", I64),
                               Field(f"{name}_grp", I64))),
                file_groups=(FileGroup(paths=(files[name],)),)),
            names=(f"key{i}", f"grp{i}"))
        ctx.broadcasts[f"bc{i}"] = BroadcastJob(rid=f"bc{i}", child=dim,
                                                schema=None)
        node = P.BroadcastJoin(
            left=node, right=P.IpcReader(schema=None, resource_id=f"bc{i}"),
            on=JoinOn(left_keys=(col(key),), right_keys=(col(f"key{i}"),)),
            join_type="inner", broadcast_side="right")
    program, inputs = spied_program(node, ctx, data_mesh(1), {})
    found = list(_eqns(jax.make_jaxpr(program)(inputs).jaxpr))
    conds = [eqn for eqn, _inside in found if eqn.primitive.name == "cond"]
    assert len(conds) == 4
    assert all(len(eqn.params["branches"]) == 2 for eqn in conds)
    loops = [inside for eqn, inside in found
             if eqn.primitive.name in ("while", "scan")]
    sides = [dict(inside)["cond"] for inside in loops
             if "cond" in dict(inside)]
    assert sides and set(sides) == {0}, sides
    # and a scatter and a gather on the direct side of each
    for wanted in ("scatter", "gather"):
        assert sum(eqn.primitive.name == wanted and ("cond", 1) in inside
                   for eqn, inside in found) >= 4, wanted
    assert program.lower(inputs).as_text().count("stablehlo.case") == 4


def test_join_probe_counter_in_the_record_the_span_and_explain(tmp_path):
    """`join_probes` / `join_probes_direct`: in the query record's totals,
    on `spmd.wait`'s args, and as `probe=direct` / `probe=search` on each
    join's line of EXPLAIN ANALYZE."""
    from auron_tpu.frontend.session import AuronSession
    from auron_tpu.it import queries
    from auron_tpu.it.datagen import generate
    from auron_tpu.it.oracle import PyArrowEngine
    catalog = generate(str(tmp_path / "tpcds"), sf=0.002)
    session = AuronSession(foreign_engine=PyArrowEngine())
    with conf.scoped({"auron.trace.enable": True}):
        res = session.execute(queries.build("q03", catalog))
    assert res.spmd
    marks = res.stage_stats["join_probes"]
    text = res.explain_analyze()
    joins = [line.split() for line in text.splitlines()
             if "_join#" in line and "build_hash_map" not in line]
    assert joins and len(joins) == len(marks)
    for label, _type, probe in joins:
        assert probe == f"probe={marks[label]}"
        assert marks[label] in ("direct", "search")
    n_direct = sum(m == "direct" for m in marks.values())
    assert n_direct >= 1              # q03 joins date_dim on d_date_sk
    rec = tracing.find_query(res.query_id)
    assert rec.metric_totals["join_probes"] == len(marks)
    assert rec.metric_totals["join_probes_direct"] == n_direct
    [wait] = [s for s in res.trace.snapshot() if s.name == "spmd.wait"]
    assert wait.args["join_probes"] == len(marks)
    assert wait.args["join_probes_direct"] == n_direct
    # the serial path reports no stage counter
    with conf.scoped({"auron.spmd.singleDevice.enable": False}):
        serial = session.execute(queries.build("q03", catalog))
    assert not serial.spmd and serial.stage_totals() == {}
    assert "join_probes" not in \
        tracing.find_query(serial.query_id).metric_totals


def test_what_crossed_to_the_device_in_the_spans_and_the_record(tmp_path):
    """A source the device cache does not serve is padded on the host
    (`shard.pad`'s `host_bytes`) and crosses once (`shard.put`'s `bytes`
    in `arrays` arrays): what was put is what was built plus the live
    mask, and what the cache then holds; summed as `shard_put_bytes` on
    `spmd.shard`, in `stage_stats` and in the query record's totals.  An
    execute whose sources are all cached pads and puts nothing."""
    from auron_tpu.frontend.session import AuronSession
    from auron_tpu.it import queries
    from auron_tpu.it.datagen import generate
    from auron_tpu.it.oracle import PyArrowEngine
    catalog = generate(str(tmp_path / "tpcds"), sf=0.002)
    session = AuronSession(foreign_engine=PyArrowEngine())
    S.clear_source_caches()
    with conf.scoped({"auron.trace.enable": True}):
        first, again = (session.execute(queries.build("q03", catalog))
                        for _ in range(2))
    assert first.spmd and again.spmd
    spans = [s for s in first.trace.snapshot() if s.dur_ns >= 0]
    [shard] = [s for s in spans if s.name == "spmd.shard"]
    pads = [s for s in spans if s.name == "shard.pad"]
    puts = [s for s in spans if s.name == "shard.put"]
    assert len(pads) == len(puts) == shard.args["placed"] > 0
    assert all(s.parent == shard.id for s in pads + puts)
    for pad, put in zip(pads, puts):
        live_bytes = pad.args["cap"]      # one device, a byte a slot
        assert put.args["bytes"] == pad.args["host_bytes"] + live_bytes
        assert put.args["arrays"] >= 3    # a column's two, and live
    put_bytes = sum(s.args["bytes"] for s in puts)
    assert shard.args["shard_put_bytes"] == put_bytes == \
        shard.args["held_bytes"]
    assert first.stage_stats["shard"]["shard_put_bytes"] == put_bytes
    assert first.stage_totals()["shard_put_bytes"] == put_bytes
    assert tracing.find_query(first.query_id).metric_totals[
        "shard_put_bytes"] == put_bytes
    names = {s.name for s in again.trace.snapshot()}
    assert "shard.pad" not in names and "shard.put" not in names
    [shard] = [s for s in again.trace.snapshot() if s.name == "spmd.shard"]
    assert (shard.args["cached"], shard.args["placed"],
            shard.args["shard_put_bytes"]) == (len(pads), 0, 0)
    assert tracing.find_query(again.query_id).metric_totals[
        "shard_put_bytes"] == 0


# name, scope path, start_ns, duration_ns
OPS = [
    ("while.1", "jit(program)/agg#0/reduce/while", 0.0, 100.0),
    ("fusion.2", "jit(program)/agg#0/reduce/while/body/add", 10.0, 30.0),
    ("fusion.3", "jit(program)/agg#0/reduce/while/body/mul", 50.0, 20.0),
    ("fusion.4 u32[8]<-u32[2],s32[8]",
     "jit(program)/agg#0/broadcast_join#1/probe/jit(_take)/gather",
     100.0, 50.0),
    ("fusion.4 u32[8]<-u32[2],s32[8]",
     "jit(program)/agg#0/broadcast_join#1/probe/jit(_take)/gather",
     150.0, 30.0),
    ("fusion.5", "jit(program)/agg#0/broadcast_join#1/build/sort",
     180.0, 20.0),
    ("fusion.6", "jit(program)/agg#0/broadcast_join#1/filter#2/and",
     200.0, 10.0),
    ("copy.7", "", 210.0, 10.0),
    ("fusion.8", "jit(program)/epilogue/gather", 220.0, 30.0),
]


@pytest.mark.parametrize("path,want", [
    ("jit(p)/agg#0/broadcast_join#1/probe/jit(_take)/gather",
     ("broadcast_join#1", "probe")),
    ("jit(p)/agg#0/broadcast_join#1/filter#2/and", ("filter#2", "")),
    ("jit(p)/agg#0/reduce/while/body/add", ("agg#0", "reduce")),
    ("jit(p)/ipc_reader#15/broadcast/all_gather",
     ("ipc_reader#15", "broadcast")),
    ("jit(p)/ipc_reader#15/broadcast/count/reduce_sum",
     ("ipc_reader#15", "broadcast/count")),
    ("jit(p)/agg#1/ipc_reader#2/exchange/scatter/sort",
     ("ipc_reader#2", "exchange/scatter")),
    ("jit(p)/agg#1/ipc_reader#2/exchange/all_to_all/all_to_all",
     ("ipc_reader#2", "exchange/all_to_all")),
    ("jit(p)/agg#1/ipc_reader#2/exchange/jit(_hash)/mul",
     ("ipc_reader#2", "exchange")),
    ("jit(p)/agg#0/reduce_sum", ("agg#0", "")),
    ("jit(p)/epilogue/gather", ("epilogue", "")),
    ("jit(p)/agg#0/broadcast_join#1/cond/branch_1_fun/build/scatter",
     ("broadcast_join#1", "build")),
    ("jit(p)/broadcast_join#1/cond/branch_0_fun/probe/while/body/gather",
     ("broadcast_join#1", "probe")),
    ("jit(p)/broadcast_join#1/cond", ("broadcast_join#1", "")),
    # an aggregate's choice of input: the compaction, the cut, the body
    ("jit(p)/agg#1/ipc_reader#2/agg#3/cond/branch_1_fun/compact/scatter",
     ("agg#3", "compact")),
    ("jit(p)/agg#1/ipc_reader#2/agg#3/cond/branch_0_fun/compact/slice",
     ("agg#3", "compact")),
    ("jit(p)/agg#3/cond/branch_1_fun/reduce/while/body/jit(_take)/gather",
     ("agg#3", "reduce")),
    # the 128-bit decimal kernels, however deep beneath the label
    ("jit(p)/agg#28/reduce/dec128/sum/jit(_take)/gather",
     ("agg#28", "dec128/sum")),
    ("jit(p)/agg#28/cond/branch_1_fun/reduce/dec128/div/while/body/sub",
     ("agg#28", "dec128/div")),
    ("jit(p)/projection#27/dec128/mul/mul", ("projection#27", "dec128/mul")),
    ("jit(p)/filter#8/dec128/cmp/lt", ("filter#8", "dec128/cmp")),
    ("jit(p)/agg#28/projection#31/dec128/cast/mul",
     ("projection#31", "dec128/cast")),
    ("jit(p)/jit(_take)/gather", (trace_cli.UNLABELLED, "")),
    ("", (trace_cli.UNLABELLED, "")),
])
def test_label_of_a_scope_path(path, want):
    assert trace_cli.label_of(path) == want


def test_device_summary_on_hand_built_events():
    assert trace_cli.self_times(OPS) == [50.0, 30.0, 20.0, 50.0, 30.0,
                                         20.0, 10.0, 10.0, 30.0]
    doc = trace_cli.device_summary(OPS)
    assert doc["busy_s"] == pytest.approx(250e-9)
    assert doc["labelled_s"] == pytest.approx(240e-9)
    rows = {(r[0], r[1]): r for r in doc["rows"]}
    assert set(rows) == {("agg#0", "reduce"), ("broadcast_join#1", "probe"),
                         ("broadcast_join#1", "build"), ("filter#2", ""),
                         ("epilogue", ""), (trace_cli.UNLABELLED, "")}
    assert sum(r[3] for r in doc["rows"]) == pytest.approx(1.0)
    # a while loop without its children; the children under their scope
    assert rows[("agg#0", "reduce")][2:5] == [pytest.approx(100e-9),
                                              pytest.approx(0.4), 3]
    assert rows[("agg#0", "reduce")][5] == "while.1"
    probe = rows[("broadcast_join#1", "probe")]
    assert probe[2] == pytest.approx(80e-9) and probe[4] == 2
    assert probe[5] == "fusion.4 u32[8]<-u32[2],s32[8]"
    assert probe[6] == pytest.approx(80e-9)
    assert rows[(trace_cli.UNLABELLED, "")][5] == "copy.7"
    # most seconds first, by group and by operation
    assert [r[2] for r in doc["rows"]] == \
        sorted((r[2] for r in doc["rows"]), reverse=True)
    assert doc["ops"][0][:3] == ["fusion.4 u32[8]<-u32[2],s32[8]",
                                 "broadcast_join#1", "probe"]


def _pb(*fields) -> bytes:
    """One protobuf message from (field number, int | bytes | str)."""
    def varint(n):
        out = bytearray()
        while True:
            out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
            n >>= 7
            if not n:
                return bytes(out)
    out = b""
    for number, value in fields:
        if isinstance(value, int):
            out += varint(number << 3) + varint(value)
        else:
            value = value.encode() if isinstance(value, str) else value
            out += varint(number << 3 | 2) + varint(len(value)) + value
    return out


def _device_plane(name, ops) -> bytes:
    """A device plane as the v5e's profile holds one: "XLA Ops" events
    that carry times only, the scope path in the `tf_op` stat of each
    event's metadata."""
    names = sorted({(op, scope) for op, scope, _s, _d in ops})
    ids = {key: i + 1 for i, key in enumerate(names)}
    metadata = [
        (4, _pb((1, ids[key]), (2, _pb(
            (1, ids[key]), (2, f"%{key[0]} = u32[8]{{0}} fusion(u32[2]{{0}} "
                               f"%a, s32[8]{{0}} %b), kind=kLoop"),
            (5, _pb((1, 7), (5, key[1]))))))) for key in names]
    events = [(4, _pb((1, ids[(op, scope)]), (2, int(start * 1000)),
                      (3, int(dur * 1000))))
              for op, scope, start, dur in ops]
    return _pb((2, name),
               (3, _pb((1, 1), (2, "XLA Ops"), (3, 0), *events)),
               *metadata,
               (5, _pb((1, 7), (2, _pb((1, 7), (2, "tf_op"))))))


def _xspace(ops) -> bytes:
    """One device plane and a host plane."""
    host = _pb((2, "/host:CPU"), (3, _pb((1, 2), (2, "main"), (3, 0))))
    return _pb((1, _device_plane("/device:TPU:0", ops)), (1, host))


def test_device_subcommand_on_a_hand_built_profile(tmp_path, capsys):
    assert trace_cli.main(["device", str(tmp_path)]) == 2
    assert "no .xplane.pb" in capsys.readouterr().err
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(_xspace(
        [(n.split()[0], scope, s, dur) for n, scope, s, dur in OPS]))
    ops = trace_cli.read_device_ops(str(tmp_path))["/device:TPU:0"]
    assert [(o[1], o[2], o[3]) for o in ops] == \
        [(scope, s, dur) for _n, scope, s, dur in OPS]
    assert ops[3][0] == "fusion.4 u32[8]<-u32[2],s32[8]"
    assert trace_cli.main(["device", str(tmp_path), "--ops", "3"]) == 0
    out = capsys.readouterr().out
    assert "9 operations" in out and "96.00 % under an operator" in out
    assert "broadcast_join#1/probe" in out and "unlabelled" in out


def test_device_subcommand_on_two_planes(tmp_path, capsys):
    """One block a device plane; what crosses devices is printed however
    short `--top` cuts the table; the planes' busy seconds side by side."""
    def ops(scatter_ns):
        return [("fusion.1", "jit(p)/agg#1/reduce/add", 0.0, 900.0),
                ("fusion.2", "jit(p)/agg#1/ipc_reader#2/exchange/scatter/"
                             "scatter", 900.0, scatter_ns),
                ("all-to-all.3", "jit(p)/agg#1/ipc_reader#2/exchange/"
                                 "all_to_all/all_to_all", 2000.0, 10.0),
                ("all-gather.4", "jit(p)/ipc_reader#5/broadcast/all_gather",
                 2010.0, 5.0)]
    d = tmp_path / "plugins" / "profile" / "run"
    d.mkdir(parents=True)
    (d / "host.xplane.pb").write_bytes(
        _pb((1, _device_plane("/device:TPU:0", ops(100.0))),
            (1, _device_plane("/device:TPU:1", ops(50.0)))))
    assert trace_cli.main(["device", str(tmp_path), "--top", "1"]) == 0
    out = capsys.readouterr().out
    assert out.count(" operations, busy ") == 2
    rows = [ln.split()[:2] for ln in out.splitlines()
            if ln.startswith("ipc_reader#")]
    assert rows == 2 * [["ipc_reader#2", "exchange/scatter"],
                        ["ipc_reader#2", "exchange/all_to_all"],
                        ["ipc_reader#5", "broadcast"]]
    assert "2 device planes: busiest /device:TPU:0 0.000001 s" in out
    assert "least busy /device:TPU:1" in out and "4.93 % less" in out


def test_short_op_name():
    hlo = ("%fusion.7 = u32[4096]{0:T(1024)} fusion(u32[8]{0} %a, "
           "s32[4096]{0} %b), kind=kLoop, calls=%fused")
    assert trace_cli.short_op_name(hlo) == \
        "fusion.7 u32[4096]<-u32[8],s32[4096]"
    assert trace_cli.short_op_name("%copy.1") == "copy.1"

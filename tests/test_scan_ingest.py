"""A stage's scan leaves reach `_shard_table` as the Arrow the scans read
(PR 31): `_materialize_scans` pulls the Arrow side of each scan's one read
loop through the serial engine's tasks, makes no device batch on the way,
and returns the table the serial engine's round trip through the device
(`execute_plan(node).to_table()`, what it returned before) gives."""

import datetime
import os
from decimal import Decimal

import jax
import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pyarrow.parquet as pq
import pytest
from pyarrow import orc

from auron_tpu import faults
from auron_tpu.columnar import arrow_interop
from auron_tpu.config import conf
from auron_tpu.ir import expr as E
from auron_tpu.ir import plan as P
from auron_tpu.ir.expr import col, lit
from auron_tpu.ir.schema import DataType as T
from auron_tpu.ir.schema import Field, Schema, to_arrow_schema
from auron_tpu.parallel import stage as S
from auron_tpu.parallel.mesh import data_mesh
from auron_tpu.runtime import counters, tracing
from auron_tpu.runtime.executor import execute_plan

FORMATS = ("parquet", "orc")
BATCH = 16          # `auron.batch.size` here: the pull size of a scan
ROWS = 40           # a file: three pulls


class _Ctx:
    exchanges = {}
    broadcasts = {}


# one column a type the device holds: (field, value of row i), nulls in
# every one
COLUMNS = {
    "i32": (T.int32(), lambda i: None if i % 5 == 0 else i - 7),
    "i64": (T.int64(), lambda i: None if i % 7 == 0 else (i - 3) << 40),
    "dec": (T.decimal(7, 2),
            lambda i: None if i % 4 == 1 else Decimal(i * 1234 - 5000) / 100),
    # NaN, -0.0, a value float32 cannot hold, an infinity
    "f64": (T.float64(),
            lambda i: (None, float("nan"), -0.0, 1 / 3, float("-inf"))[i]
            if i < 5 else i * 0.1),
    "day": (T.date32(), lambda i: None if i % 6 == 2 else
            datetime.date(1998, 1, 1) + datetime.timedelta(days=40 * i)),
    "ts": (T.timestamp_us(), lambda i: None if i % 6 == 3 else
           datetime.datetime(2001, 2, 3, 4, 5, 6, i)),
    "flag": (T.bool_(), lambda i: None if i % 3 == 0 else i % 2 == 0),
    # empty, null, more than one byte a character
    "s": (T.string(), lambda i: None if i % 5 == 3 else
          "" if i % 5 == 1 else "straße" * (i % 4)),
}


def schema_of(names) -> Schema:
    return Schema(tuple(Field(n, COLUMNS[n][0]) for n in names))


def rows_table(names, start=0, n=ROWS) -> pa.Table:
    schema = to_arrow_schema(schema_of(names))
    return pa.table(
        [pa.array([COLUMNS[c][1](i) for i in range(start, start + n)],
                  type=schema.field(c).type) for c in names], schema=schema)


def write(fmt, table, path) -> str:
    path = f"{path}.{fmt}"
    if fmt == "parquet":
        pq.write_table(table, path, row_group_size=BATCH)
    else:
        orc.write_table(table, path)
    return path


def scan(fmt, schema, groups, **kw):
    node = P.ParquetScan if fmt == "parquet" else P.OrcScan
    return node(schema=schema, file_groups=tuple(
        P.FileGroup(paths=tuple(g)) for g in groups), **kw)


def same_table(a: pa.Table, b: pa.Table) -> bool:
    """`pa.Table.equals`, schema included, with doubles compared by their
    bits (Arrow holds a NaN unequal to itself)."""
    if not a.schema.equals(b.schema) or a.num_rows != b.num_rows:
        return False
    for x, y in zip(a.columns, b.columns):
        if pa.types.is_floating(x.type):
            if not pc.is_valid(x).equals(pc.is_valid(y)):
                return False
            x, y = (pa.chunked_array([np.asarray(
                pc.fill_null(c, 0.0).combine_chunks()).view(np.uint64)])
                for c in (x, y))
        if not x.equals(y):
            return False
    return True


def ingest(node):
    """`_materialize_scans` of one leaf from cold caches: its table, what
    it says it read, and how often an Arrow batch became a device batch
    meanwhile."""
    S.clear_source_caches()
    made = []
    real = arrow_interop.arrow_to_batch
    with pytest.MonkeyPatch.context() as m:
        m.setattr(arrow_interop, "arrow_to_batch",
                  lambda *a, **k: made.append(1) or real(*a, **k))
        rids, tables, read = S._materialize_scans(node, _Ctx())
    assert rids == {id(node): "scan:0"} and list(tables) == ["scan:0"]
    return tables["scan:0"], read, len(made)


def serial(node) -> pa.Table:
    """The serial engine's read, every batch through the device and back,
    partition by partition in file-group order."""
    n_parts = max(1, len(node.file_groups))
    parts = [execute_plan(node, partition_id=pid, num_partitions=n_parts)
             for pid in range(n_parts)]
    return pa.Table.from_batches([b for res in parts for b in res.batches],
                                 schema=parts[0].schema)


def shard_arrays(table, n_dev=2):
    schema, cols, live, cap = S._shard_table(table, data_mesh(n_dev), "parts")
    return schema, cap, [np.asarray(x) for x in jax.tree.leaves((cols, live))]


def assert_same_shards(got, want):
    s1, cap1, a1 = shard_arrays(got)
    s2, cap2, a2 = shard_arrays(want)
    assert s1 == s2 and cap1 == cap2 and len(a1) == len(a2)
    for x, y in zip(a1, a2):
        assert x.dtype == y.dtype and x.shape == y.shape
        assert x.tobytes() == y.tobytes()


def _case_types(fmt, d, names):
    t = rows_table(names)
    return scan(fmt, schema_of(names), [[write(fmt, t, d / "a")]]), t


def case_ints(fmt, d):
    return _case_types(fmt, d, ("i32", "i64"))


def case_decimal(fmt, d):
    return _case_types(fmt, d, ("dec",))


def case_double(fmt, d):
    return _case_types(fmt, d, ("f64",))


def case_date_timestamp_bool(fmt, d):
    return _case_types(fmt, d, ("day", "ts", "flag"))


def case_strings(fmt, d):
    return _case_types(fmt, d, ("s",))


def case_every_type(fmt, d):
    return _case_types(fmt, d, tuple(COLUMNS))


def case_projection(fmt, d):
    names = tuple(COLUMNS)
    t = rows_table(names)
    node = scan(fmt, schema_of(names), [[write(fmt, t, d / "a")]],
                projection=(7, 2, 0))
    return node, t.select(["s", "dec", "i32"])


def case_absent_column(fmt, d):
    """The file lacks `dec` and holds its columns in another order: the
    scan hands on nulls for it, in the plan's order."""
    names = ("i32", "dec", "s")
    t = rows_table(names)
    path = write(fmt, t.select(["s", "i32"]), d / "a")
    want = t.set_column(1, "dec", pa.nulls(ROWS, t.schema.field("dec").type))
    return scan(fmt, schema_of(names), [[path]]), want


def case_partition_values(fmt, d):
    """Hive partition values, one tuple a file group (the parquet scan's;
    the ORC node has none)."""
    names = ("i32", "s")
    parts = Schema((Field("p_year", T.int32()), Field("p_name", T.string())))
    values = ((2000, "a"), (None, "b"))
    tables = [rows_table(names, start=k * ROWS) for k in range(2)]
    node = scan(fmt, schema_of(names),
                [[write(fmt, t, d / f"a{k}")] for k, t in enumerate(tables)],
                partition_schema=parts, partition_values=values)
    full = to_arrow_schema(schema_of(names).concat(parts))
    want = pa.concat_tables([
        pa.table(t.columns + [pa.array([v] * ROWS, full.field(f.name).type)
                              for f, v in zip(parts, vs)], schema=full)
        for t, vs in zip(tables, values)])
    return node, want


def case_pruned_row_group(fmt, d):
    """A pushed predicate that statistics answer for whole row groups: the
    parquet scan reads one row group of three (the ORC scan reads all)."""
    names = ("i64", "s")
    schema = to_arrow_schema(schema_of(names))
    t = pa.table([pa.array(range(ROWS), schema.field("i64").type),
                  rows_table(("s",))["s"]], schema=schema)
    node = scan(fmt, schema_of(names), [[write(fmt, t, d / "a")]],
                predicate=E.BinaryExpr(op=">=", left=col("i64"),
                                       right=lit(2 * BATCH)))
    return node, t.slice(2 * BATCH) if fmt == "parquet" else t


def case_file_groups(fmt, d):
    """Two file groups of two files, a group of no file and one whose file
    holds no row: partitions in the groups' order, no batch of no rows."""
    names = ("i32", "f64", "s")
    tables = [rows_table(names, start=k * ROWS, n=ROWS - 9 * k)
              for k in range(4)]
    paths = [write(fmt, t, d / f"a{k}") for k, t in enumerate(tables)]
    empty = write(fmt, tables[0].slice(0, 0), d / "none")
    node = scan(fmt, schema_of(names),
                [paths[:2], [], [empty], paths[2:]])
    return node, pa.concat_tables(tables)


CASES = {f.__name__[len("case_"):]: f for f in (
    case_ints, case_decimal, case_double, case_date_timestamp_bool,
    case_strings, case_every_type, case_projection, case_absent_column,
    case_partition_values, case_pruned_row_group, case_file_groups)}
# (format, case): ORC has no partition values to hand on
TABLE_CASES = [(f, c) for f in FORMATS for c in CASES
               if (f, c) != ("orc", "partition_values")]


@pytest.fixture(autouse=True)
def small_batches():
    with conf.scoped({"auron.batch.size": BATCH}):
        yield
    S.clear_source_caches()


@pytest.mark.parametrize("fmt,case", TABLE_CASES)
def test_ingest_hands_on_the_arrow_the_scan_read(fmt, case, tmp_path):
    node, file_rows = CASES[case](fmt, tmp_path)
    got, read, device_batches = ingest(node)
    # nothing of it went to the device, and the counter says so
    assert device_batches == 0 and read["device_batches"] == 0
    want = serial(node)
    assert want.num_rows and same_table(got, want)
    # and both are the file's rows under the plan's schema
    assert same_table(got.combine_chunks(), file_rows.combine_chunks())
    assert_same_shards(got, want)
    kept = [b for b in got.to_batches() if b.num_rows]
    assert read == {
        "scans": 1, "cached": 0, "tasks": max(1, len(node.file_groups)),
        "batches": len(kept), "rows": want.num_rows, "bytes": got.nbytes,
        "device_batches": 0,
        # and `_SCAN_TABLES` holds it, inside its budget
        "evicted": 0, "held_bytes": got.nbytes, "over_budget_bytes": 0}
    assert len(kept) == len(got.to_batches()) and \
        all(b.num_rows <= BATCH for b in kept)
    if case == "pruned_row_group" and fmt == "parquet":
        assert read["batches"] == 1


@pytest.mark.parametrize("fmt", FORMATS)
def test_a_leaf_of_no_row_keeps_its_schema(fmt, tmp_path):
    names = ("i32", "dec", "s")
    path = write(fmt, rows_table(names, n=0), tmp_path / "a")
    node = scan(fmt, schema_of(names), [[path]])
    got, read, _ = ingest(node)
    assert got.num_rows == 0 and got.schema.equals(serial(node).schema)
    assert (read["tasks"], read["batches"], read["rows"]) == (1, 0, 0)


# Where the serial engine's round trip is NOT an identity, or no trip at
# all, the direct path hands on what the file holds: what `_shard_table`
# would have been given had the trip been lossless.

@pytest.mark.parametrize("fmt", FORMATS)
def test_doubles_arrive_as_the_file_holds_them_whatever_the_sidecar(
        fmt, tmp_path):
    """With `auron.sort.f64.exactbits` off a device without 64-bit floats
    demotes a double on the way up and the serial trip brings the demoted
    value back; the ingest never goes up, so it holds the file's bits
    under every setting.  (This backend holds float64: here the serial
    trip is an identity under every setting too.)"""
    node, file_rows = case_double(fmt, tmp_path)
    for mode in ("off", "on", "auto"):
        with conf.scoped({"auron.sort.f64.exactbits": mode}):
            got, read, _ = ingest(node)
            assert same_table(got, file_rows) and read["device_batches"] == 0
            if jax.default_backend() == "cpu":
                assert same_table(got, serial(node))


@pytest.mark.parametrize("fmt", FORMATS)
@pytest.mark.parametrize("what", ["wide-string", "decimal-38"])
def test_a_host_resident_column_is_handed_on_and_the_shard_refuses_it(
        fmt, what, tmp_path):
    """A string wider than `auron.string.device.max.width` and a
    decimal(p > 18) never were on the serial engine's device: it carries
    them as a `HostColumn`, Arrow in and the same Arrow out.  Both paths
    hand them on as the file holds them; `_shard_table` raises
    `SpmdUnsupported` for the string (the session then runs the plan
    serially) and, since PR 35, holds the decimal as two words a value."""
    if what == "wide-string":
        dtype, values = T.string(), ["x" * 40, None, "", "y" * 3]
    else:
        dtype = T.decimal(38, 4)
        values = [Decimal("12345678901234567890123456.7891"), None,
                  Decimal("-0.0001"), Decimal(7)]
    schema = Schema((Field("k", T.int64()), Field("v", dtype)))
    at = to_arrow_schema(schema)
    t = pa.table([pa.array(range(4), at.field("k").type),
                  pa.array(values, at.field("v").type)], schema=at)
    node = scan(fmt, schema, [[write(fmt, t, tmp_path / "a")]])
    with conf.scoped({"auron.string.device.max.width": 32}):
        got, read, _ = ingest(node)
        assert same_table(got, t) and same_table(got, serial(node))
        assert read["device_batches"] == 0
        for table in (got, serial(node)):
            if what == "decimal-38":
                _schema, cols, _live, _cap = S._shard_table(
                    table, data_mesh(1), "parts")
                assert [(int(h) << 64) + int(lo) for h, lo in zip(
                    cols[1].hi[:4], cols[1].lo[:4])] == [
                    123456789012345678901234567891, 0, -1, 70000]
                assert cols[1].validity[:4].tolist() == [
                    True, False, True, True]
                continue
            with pytest.raises(S.SpmdUnsupported, match="host-resident"):
                S._shard_table(table, data_mesh(1), "parts")


# -- what the scan tasks keep of execute_task ------------------------------

@pytest.mark.parametrize("fmt", FORMATS)
def test_an_injected_open_fault_reaches_the_retry_tier(fmt, tmp_path):
    """`scan.<format>.open` lies outside the corrupted-file catch on the
    Arrow side of the loop as on the other: an injected io fault is
    retried by the pool's task policy and never read as a skipped file."""
    node, file_rows = case_file_groups(fmt, tmp_path)
    spec = f"scan.{fmt}.open:io:p=1,max=2,seed=1"
    faults.reset(spec)
    retried = counters.get("tasks_retried")
    started = counters.get("tasks_started")
    with conf.scoped({"auron.faults.spec": spec,
                      "auron.ignore.corrupted.files": True,
                      "auron.task.retries": 3,
                      "auron.retry.backoff.base.ms": 1.0}):
        got, read, _ = ingest(node)
    assert faults.registry_for(spec).injected_total() == 2
    faults.reset()
    assert same_table(got.combine_chunks(), file_rows.combine_chunks())
    assert counters.get("tasks_retried") - retried == 2
    assert counters.get("tasks_started") - started == read["tasks"] + 2


def test_a_corrupted_file_is_skipped_or_raised_as_the_option_says(tmp_path):
    names = ("i32", "s")
    good = write("parquet", rows_table(names), tmp_path / "good")
    bad = str(tmp_path / "bad.parquet")
    with open(bad, "wb") as f:
        f.write(b"not a parquet file")
    node = scan("parquet", schema_of(names), [[bad, good]])
    with conf.scoped({"auron.ignore.corrupted.files": True}):
        got, _read, _ = ingest(node)
        assert same_table(got, serial(node)) and got.num_rows == ROWS
    with conf.scoped({"auron.ignore.corrupted.files": False}):
        with pytest.raises(Exception, match="(?i)parquet"):
            ingest(node)


def test_a_scan_task_is_a_task(tmp_path):
    """`task.execute` with stage and partition under `spmd.ingest`,
    `scan.decode` and `scan.to_device` under it and no `task.to_host`;
    the verified plan, the attempt counters, the pruning metrics."""
    node, _ = case_pruned_row_group("parquet", tmp_path)
    rec = tracing.TraceRecorder("ingest", max_events=1000)
    started = counters.get("tasks_started")
    completed = counters.get("tasks_completed")
    with tracing.trace_scope(recorder=rec, query_id="ingest") as scope:
        with tracing.span("spmd.ingest", cat="spmd"):
            S.clear_source_caches()
            S._materialize_scans(node, _Ctx())
        assert scope.stats.snapshot()["attempts"] == 1
    assert counters.get("tasks_started") - started == 1
    assert counters.get("tasks_completed") - completed == 1
    spans = [s for s in rec.snapshot() if s.dur_ns >= 0]
    by_id = {s.id: s for s in spans}
    [task] = [s for s in spans if s.name == "task.execute"]
    assert by_id[task.parent].name == "spmd.ingest"
    assert (task.args["stage"], task.args["partition"]) == (0, 0)
    names = [s.name for s in spans if s.parent == task.id]
    assert "task.plan" in names and "task.to_host" not in names
    # the verified plan: under the runtime's construction (PR 37)
    [verify] = [s for s in spans if s.name == "plan.verify"]
    assert by_id[verify.parent].name == "task.plan"
    # a pull a batch and the one that finds the end
    assert names.count("scan.decode") == 2
    assert names.count("scan.to_device") == 1
    done = [e for e in rec.snapshot() if e.name == "op.complete"]
    assert [(e.args["rows"], e.args["batches"]) for e in done] == \
        [(ROWS - 2 * BATCH, 1)]


def test_the_serial_engine_still_reads_through_the_device(tmp_path,
                                                          monkeypatch):
    """`execute()` is the same loop with `Batch.from_arrow` on each item,
    inside `scan.to_device`; its task brings every batch back under
    `task.to_host` and counts it."""
    node, _ = case_every_type("parquet", tmp_path)
    made = []
    real = arrow_interop.arrow_to_batch
    monkeypatch.setattr(arrow_interop, "arrow_to_batch",
                        lambda *a, **k: made.append(1) or real(*a, **k))
    rec = tracing.TraceRecorder("serial", max_events=1000)
    with tracing.trace_scope(recorder=rec, query_id="serial"):
        res = execute_plan(node)
    pulls = -(-ROWS // BATCH)
    assert len(made) == pulls and res.device_batches == pulls
    assert res.metrics.get("output_rows") == ROWS
    names = [s.name for s in rec.snapshot() if s.dur_ns >= 0]
    assert names.count("scan.to_device") == pulls
    assert names.count("task.to_host") == pulls
    # asked for Arrow, the same task hands on what the scan read
    direct = execute_plan(node, arrow=True)
    assert direct.device_batches == 0 and len(made) == pulls
    assert same_table(direct.to_table(), res.to_table())
    assert direct.metrics.get("output_rows") == ROWS
    assert direct.metrics.get("output_batches") == pulls


# -- the counter, through a stage execute and a session --------------------

def _filter_plan(fmt, d):
    names = ("i64", "s")
    schema = to_arrow_schema(schema_of(names))
    tables = [pa.table([pa.array(range(k * ROWS, (k + 1) * ROWS),
                                 schema.field("i64").type),
                        rows_table(("s",))["s"]], schema=schema)
              for k in range(2)]
    node = scan(fmt, schema_of(names),
                [[write(fmt, t, d / f"a{k}")] for k, t in enumerate(tables)])
    return P.Filter(child=node, predicates=(
        E.BinaryExpr(op=">=", left=col("i64"), right=lit(10)),)), node


def _traced(plan, stats=None):
    rec = tracing.TraceRecorder("q", max_events=10_000)
    with tracing.trace_scope(recorder=rec, query_id="q"):
        with tracing.span("query", cat="query", query_id="q"):
            table = S.execute_plan_spmd(plan, _Ctx(), data_mesh(2), {},
                                        stats=stats)
    spans = [s for s in rec.snapshot() if s.dur_ns >= 0]
    [span] = [s for s in spans if s.name == "spmd.ingest"]
    tasks = [s for s in spans if s.name == "task.execute"
             and s.parent == span.id]
    return table, span, tasks


@pytest.mark.parametrize("fmt", FORMATS)
def test_spmd_ingest_reports_what_it_read_and_what_was_cached(fmt, tmp_path):
    plan, node = _filter_plan(fmt, tmp_path)
    S.clear_source_caches()
    stats = {}
    table, span, tasks = _traced(plan, stats)
    assert table.num_rows == 2 * ROWS - 10
    pulls = 2 * -(-ROWS // BATCH)
    assert len(tasks) == 2
    assert {k: span.args[k] for k in S.INGEST_COUNTS if k != "bytes"} == {
        "scans": 1, "cached": 0, "tasks": 2, "batches": pulls,
        "rows": 2 * ROWS, "device_batches": 0}
    assert span.args["bytes"] == \
        S._SCAN_TABLES.get(node, S._scan_files_fp(node)).nbytes > 0
    assert stats["ingest"] == {k: span.args[k]
                               for k in S.INGEST_COUNTS + S.CACHE_STATE}
    assert S.stage_totals(stats)["scan_rows"] == 2 * ROWS
    # unchanged files: every leaf out of `_SCAN_TABLES`, no task
    again = {}
    table2, span2, tasks2 = _traced(plan, again)
    assert table2.equals(table) and tasks2 == []
    assert {k: span2.args[k] for k in S.INGEST_COUNTS} == {
        "scans": 1, "cached": 1, "tasks": 0, "batches": 0, "rows": 0,
        "bytes": 0, "device_batches": 0}
    assert S.stage_totals(again)["scan_rows"] == 0
    # a replaced file: read again
    st = os.stat(node.file_groups[0].paths[0])
    os.utime(node.file_groups[0].paths[0],
             ns=(st.st_atime_ns, st.st_mtime_ns + 1_000_000))
    _t, span3, tasks3 = _traced(plan)
    assert (span3.args["cached"], span3.args["rows"], len(tasks3)) == \
        (0, 2 * ROWS, 2)


def test_scan_totals_in_the_query_record(tmp_path):
    """`scan_rows`, `scan_batches`, `scan_device_batches` where
    `join_probes_direct` goes: `stage_totals` and
    `QueryRecord.metric_totals`; none on the serial path."""
    from auron_tpu.frontend.session import AuronSession
    from auron_tpu.it import queries
    from auron_tpu.it.datagen import generate
    from auron_tpu.it.oracle import PyArrowEngine
    with conf.scoped({"auron.batch.size": 8192}):
        catalog = generate(str(tmp_path / "tpcds"), sf=0.002)
        session = AuronSession(foreign_engine=PyArrowEngine())
        S.clear_source_caches()
        res = session.execute(queries.build("q03", catalog))
        assert res.spmd
        totals = tracing.find_query(res.query_id).metric_totals
        ingest = res.stage_stats["ingest"]
        assert ingest["scans"] == 3 and ingest["cached"] == 0
        assert ingest["rows"] > 0 and ingest["device_batches"] == 0
        assert {k: totals[k] for k in totals if k.startswith("scan_")} == {
            "scan_rows": ingest["rows"], "scan_batches": ingest["batches"],
            "scan_device_batches": 0, "scan_cached": 0}
        assert res.stage_totals()["scan_rows"] == ingest["rows"]
        warm = session.execute(queries.build("q03", catalog))
        assert warm.stage_stats["ingest"]["cached"] == 3
        assert warm.stage_totals()["scan_rows"] == 0
        with conf.scoped({"auron.spmd.singleDevice.enable": False}):
            ser = session.execute(queries.build("q03", catalog))
        assert not ser.spmd and "scan_rows" not in \
            tracing.find_query(ser.query_id).metric_totals

"""The four-device deployment of the benchmark's query 7 (configuration
`tpcds-sf1-4chip`, cell `tpcds-sf1-mesh4.q07`) at the configuration's
`rehearse_rows` on the suite's virtual CPU devices: the answer against the
cell's plain reference and against the one-device program's, where the
rows lie before and after what crosses devices, and what the program
counts of it."""

import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

import jax
from jax.sharding import NamedSharding, PartitionSpec as PS

from auron_tpu import config
from auron_tpu.columnar.batch import DeviceColumn, bucket_capacity
from auron_tpu.exprs import hashing as H
from auron_tpu.frontend.converters import ShuffleJob
from auron_tpu.frontend.session import AuronSession
from auron_tpu.ir import plan as P
from auron_tpu.ir.expr import col
from auron_tpu.ir.schema import DataType, from_arrow_schema
from auron_tpu.it.oracle import PyArrowEngine
from auron_tpu.parallel import stage as S
from auron_tpu.parallel.exchange import bounded_quota
from auron_tpu.parallel.mesh import data_mesh
from auron_tpu.runtime import tracing
from benchmarks.harness import cells, compare, datagen
from benchmarks.queries import q07

I64 = DataType.int64()
N_DEV = 4
CELL = "tpcds-sf1-mesh4.q07"
SEEDS = (5, 2**31 + 11)


class _Ctx:
    def __init__(self):
        self.exchanges = {}
        self.broadcasts = {}


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def runs(cell, tmp_path_factory):
    """seed -> (catalog, parameters, the session's results on 4 devices:
    the first execute and one after warm-up)."""
    assert cell.chips == cell.config["mesh_devices"] == N_DEV
    params = cell.traffic["param_sets"][0]
    out = {}
    for seed in SEEDS:
        cat = datagen.generate(
            str(tmp_path_factory.mktemp(f"q07-{seed}")), q07.SCANS,
            cell.config["rehearse_rows"], cell.config["data_seed"], seed)
        session = AuronSession(foreign_engine=PyArrowEngine())
        plan = q07.build_plan(cat, params)
        with config.conf.scoped({"auron.trace.enable": True}):
            got = [session.execute(plan, mesh=data_mesh(N_DEV))
                   for _ in range(2)]
        out[seed] = (cat, params, session, plan, got)
    return out


# -- (i) the answer is the reference's ------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_four_devices_give_the_reference_answer(runs, seed):
    cat, params, _session, _plan, got = runs[seed]
    want = q07.reference(cat.read, params)
    for res in got:
        assert res.spmd and res.spmd_rejection is None
        verdict = compare.judge(compare.compare_tables(res.table, want),
                                q07.LIMITS)
        assert verdict["ok"], verdict
    warm = tracing.find_query(got[1].query_id)
    assert (warm.retries, warm.fallbacks) == (0, 0)
    assert warm.metric_totals.get("num_fallbacks", 0) == 0


# -- (ii) one device and four give one table -------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_one_device_and_four_give_the_same_table(runs, seed):
    """Decimals, and the double bit for bit: the averages sum integers
    held as doubles, so the order of summation does not show."""
    _cat, _params, session, plan, got = runs[seed]
    one = session.execute(plan, mesh=data_mesh(1))
    assert one.spmd and got[1].table.num_rows > 0
    assert one.table.schema == got[1].table.schema
    assert one.table.equals(got[1].table)
    agg1 = [np.asarray(t.column("agg1").to_numpy(), np.float64).view(
        np.uint64) for t in (one.table, got[1].table)]
    assert (agg1[0] == agg1[1]).all()


# -- (iii) the shares add up ------------------------------------------------

def _put(mesh, tree):
    return jax.tree.map(
        lambda x: jax.device_put(x, NamedSharding(mesh, PS("parts"))), tree)


@pytest.mark.parametrize("table", sorted(q07.SCANS))
def test_every_source_row_is_live_on_exactly_one_device(runs, table):
    cat, _params, _s, _p, _got = runs[SEEDS[0]]
    key = q07.SCANS[table][0]
    rows = cat.read(table, [key])
    _schema, cols, live, cap = S._shard_table(rows, data_mesh(N_DEV),
                                              "parts")
    dealt = S._rows_per_device(rows.num_rows, N_DEV)
    assert sum(dealt) == rows.num_rows and max(dealt) - min(dealt) <= \
        N_DEV and cap == bucket_capacity(max(dealt))
    live = np.asarray(live).reshape(N_DEV, cap)
    data = np.asarray(cols[0].data).reshape(N_DEV, cap)
    valid = np.asarray(cols[0].validity).reshape(N_DEV, cap)
    # file order, device after device; every pad row dead and null
    assert [int(m.sum()) for m in live] == dealt
    assert all(m[:n].all() and not m[n:].any() for m, n in zip(live, dealt))
    assert not valid[~live].any()
    want = rows.column(key).combine_chunks()
    got = pa.array(data[live], mask=~valid[live])
    assert got.equals(want.cast(got.type))


def _exchange_alone(table, key):
    """`table` through `_StageTracer._exchange` and nothing else, hashed
    on `key` over four devices: (the key column and live mask that each
    device holds afterwards, the boundary's counts, its guard)."""
    mesh = data_mesh(N_DEV)
    schema, cols, live, _cap = S._shard_table(table, mesh, "parts")
    part = P.Partitioning(mode="hash", num_partitions=N_DEV,
                          expressions=(col(key),))
    box = []

    def body(cols, live):
        tracer = S._StageTracer(_Ctx(), {}, "parts", N_DEV, {})
        out = tracer._exchange(S.DeviceTable(schema, cols, live), part,
                               "ipc_reader#0")
        [(what, counts)] = tracer.crossings
        box[:] = [what]
        return (out.cols[schema.names().index(key)], out.live, counts,
                tracer.guards[0])

    cols, live = _put(mesh, (cols, live))
    kcol, out_live, counts, guard = jax.jit(jax.shard_map(
        body, mesh=mesh,
        in_specs=(jax.tree.map(lambda _: PS("parts"), cols), PS("parts")),
        out_specs=(PS("parts"), PS("parts"), PS(), PS()),
        check_vma=False))(cols, live)
    stats = S._crossing_stats(box, np.asarray(counts))
    return kcol, np.asarray(out_live).reshape(N_DEV, -1), \
        stats["exchanges"]["ipc_reader#0"], bool(guard)


def test_across_the_exchange_every_item_id_ends_on_one_device(runs):
    """The partial aggregate's exchange hashes on i_item_id: the item
    table's ids (each on two or three rows, dsdgen's revisions) through
    the same exchange — rows in = rows out, an id on one device alone."""
    cat, _params, _s, _p, _got = runs[SEEDS[0]]
    items = cat.read("item", ["i_item_sk", "i_item_id"])
    kcol, live, stats, tripped = _exchange_alone(items, "i_item_id")
    assert not tripped
    data = np.asarray(kcol.data).reshape(N_DEV, live.shape[1], -1)
    lengths = np.asarray(kcol.lengths).reshape(N_DEV, -1)
    on_device = [sorted(bytes(r[:n]).decode()
                        for r, n in zip(data[d][live[d]],
                                        lengths[d][live[d]]))
                 for d in range(N_DEV)]
    want = sorted(items.column("i_item_id").to_pylist())
    assert sorted(sum(on_device, [])) == want
    assert len(set(want)) < len(want)           # ids repeat
    homes = [set(ids) for ids in on_device]
    assert sum(len(h) for h in homes) == len(set(want))
    assert all(homes)                           # and spread over all four
    assert stats["rows"] == items.num_rows
    assert stats["rows_recv_max"] == max(len(ids) for ids in on_device)
    assert stats["fill_pct"] == 100.0 * stats["block_rows_max"] \
        / stats["quota"] < 100.0


# -- (iv) the counters say what happened ------------------------------------

def _exchange_root(table, key):
    """(plan, ctx): `table` behind a hash exchange on `key`, and no more."""
    ctx = _Ctx()
    src = P.FFIReader(schema=from_arrow_schema(table.schema),
                      resource_id="t")
    ctx.exchanges["ex"] = ShuffleJob(
        rid="ex", child=src, schema=None,
        partitioning=P.Partitioning(mode="hash", num_partitions=N_DEV,
                                    expressions=(col(key),)))
    return P.IpcReader(schema=None, resource_id="ex"), ctx


def _destination(keys):
    return np.asarray(H.pmod(H.hash_columns(
        [DeviceColumn(I64, jax.numpy.asarray(keys),
                      jax.numpy.ones(len(keys), bool))], seed=42), N_DEV))


def test_uniform_keys_move_three_quarters_of_the_rows():
    n = 4000
    table = pa.table({"k": np.arange(n, dtype=np.int64),
                      "v": np.arange(n, dtype=np.float64)})
    plan, ctx = _exchange_root(table, "k")
    stats = {}
    got = S.execute_plan_spmd(plan, ctx, data_mesh(N_DEV), {"t": table},
                              stats=stats)
    assert sorted(got.column("k").to_pylist()) == list(range(n))
    [ex] = stats["exchanges"].values()
    cap = bucket_capacity(n // N_DEV)
    dest = _destination(np.arange(n, dtype=np.int64))
    home = np.arange(n) // (n // N_DEV)         # the device a row starts on
    assert ex["rows"] == n
    assert ex["rows_moved"] == int((dest != home).sum())
    assert 0.70 * n < ex["rows_moved"] < 0.80 * n
    assert ex["rows_recv_max"] == int(np.bincount(dest).max())
    assert ex["quota"] == bounded_quota(cap, N_DEV)
    assert ex["block_rows_max"] == max(
        int(((dest == d) & (home == s)).sum())
        for d in range(N_DEV) for s in range(N_DEV))
    assert 0.0 < ex["fill_pct"] < 100.0
    # two 8-byte columns with their validity, and the live mask
    assert ex["moved_bytes"] == ex["rows_moved"] * (2 * 9 + 1)
    assert ex["buffer_bytes"] == N_DEV * ex["quota"] * (2 * 9 + 1)
    assert list(stats["sources"].values()) == [{
        "rows": n, "cap": cap, "rows_max": n // N_DEV,
        "rows_min": n // N_DEV}]
    assert "broadcasts" not in stats
    assert S.crossing_totals(stats) == {
        "exchange_rows": n, "exchange_rows_moved": ex["rows_moved"],
        "exchange_buffer_bytes": ex["buffer_bytes"],
        "exchange_fill_pct_max": ex["fill_pct"]}


def test_keys_of_one_destination_fill_past_the_quota_and_are_discarded():
    """Every key hashes to device 2: a device's block for it overflows
    its quota, the guard discards the result (never a truncated one), and
    the counter says how far past the edge the block was."""
    pool = np.arange(40_000, dtype=np.int64)
    keys = pool[_destination(pool) == 2][:4000]
    assert len(keys) == 4000
    table = pa.table({"k": keys})
    plan, ctx = _exchange_root(table, "k")
    stats = {}
    with pytest.raises(S.SpmdGuardTripped) as e:
        S.execute_plan_spmd(plan, ctx, data_mesh(N_DEV), {"t": table},
                            stats=stats)
    assert e.value.hard and "result discarded" in str(e.value)
    [ex] = stats["exchanges"].values()
    assert ex["rows"] == 4000 and ex["rows_moved"] == 3000
    assert ex["block_rows_max"] == 1000 > ex["quota"]
    assert S.crossing_totals(stats)["exchange_fill_pct_max"] == \
        100.0 * 1000 / ex["quota"] >= 100.0
    # what arrived was cut at the quota: the reason it is thrown away
    assert ex["rows_recv_max"] == N_DEV * ex["quota"] < 4000


@pytest.mark.parametrize("seed", SEEDS)
def test_a_broadcast_counts_the_build_sides_live_rows(runs, seed):
    cat, params, _s, _p, got = runs[seed]
    read = cat.read
    cd = read("customer_demographics", q07.SCANS["customer_demographics"])
    pr = read("promotion", q07.SCANS["promotion"])
    live = {
        "customer_demographics": pc.sum(pc.and_(pc.and_(
            pc.equal(cd["cd_gender"], params["GEN"]),
            pc.equal(cd["cd_marital_status"], params["MS"])),
            pc.equal(cd["cd_education_status"], params["ES"]))).as_py(),
        "date_dim": pc.sum(pc.equal(
            read("date_dim", ["d_year"])["d_year"],
            int(params["YEAR"]))).as_py(),
        "item": cat.tables["item"].rows,
        "promotion": pc.sum(pc.or_kleene(
            pc.equal(pr["p_channel_email"], "N"),
            pc.equal(pr["p_channel_event"], "N"))).as_py(),
    }
    slots = {t: N_DEV * bucket_capacity(-(-cat.tables[t].rows // N_DEV))
             for t in live}
    for res in got:
        bc = res.stage_stats["broadcasts"]
        assert len(bc) == 4
        assert sorted((b["rows"], b["slots"]) for b in bc.values()) == \
            sorted((live[t], slots[t]) for t in live)
        assert all(b["buffer_bytes"] >= 9 * b["slots"] for b in bc.values())


# -- where the counters go ---------------------------------------------------

_TOTALS = ("exchange_rows", "exchange_rows_moved", "exchange_buffer_bytes",
           "exchange_fill_pct_max", "broadcast_rows", "broadcast_slots",
           "broadcast_buffer_bytes")


def _wait_args(res):
    [wait] = [s for s in res.trace.snapshot() if s.name == "spmd.wait"]
    return wait.args


def test_counters_reach_the_record_the_span_and_explain_analyze(runs):
    _cat, _params, _session, _plan, got = runs[SEEDS[0]]
    res = got[1]
    totals = tracing.find_query(res.query_id).metric_totals
    assert res.stage_totals() == {k: totals[k] for k in res.stage_totals()}
    assert set(_TOTALS) <= set(totals)
    assert (totals["join_probes"], totals["join_probes_direct"]) == (4, 4)
    # 10,000 fact rows a device in 16,384 slots: the chain's one rung is
    # 2,048 rows (a sixty-fourth, 256, is under the least bucket)
    assert (totals["join_chains"], totals["join_chains_compact"]) == (1, 1)
    assert res.stage_stats["join_chains"]["broadcast_join#11"]["rows"] == \
        2048
    [ex] = res.stage_stats["exchanges"].values()
    assert totals["exchange_rows"] == ex["rows"] >= res.table.num_rows
    assert totals["broadcast_rows"] == sum(
        b["rows"] for b in res.stage_stats["broadcasts"].values())
    args = _wait_args(res)
    assert {k: args[k] for k in _TOTALS} == {k: totals[k] for k in _TOTALS}
    assert args["join_probes_direct"] == 4
    spans = {s.name: s.args for s in got[0].trace.snapshot()
             if s.name in ("spmd.shard", "shard.pad")}
    assert spans["spmd.shard"]["device_rows_max"] >= \
        spans["spmd.shard"]["device_rows_min"] > 0
    assert spans["shard.pad"]["rows_max"] >= spans["shard.pad"]["rows_min"]
    text = res.explain_analyze()
    exchange = [ln for ln in text.splitlines() if "exchange:hash" in ln]
    assert len(exchange) == 1 and f"rows={ex['rows']} " in exchange[0] \
        and f"quota={ex['quota']} " in exchange[0] and "fill=" in exchange[0]
    broadcasts = [ln for ln in text.splitlines() if " broadcast " in ln]
    assert len(broadcasts) == 4
    assert all(" rows=" in ln and " slots=" in ln for ln in broadcasts)
    assert text.count("probe=direct") == 4


# -- (v) one device -----------------------------------------------------------

def test_one_device_counts_nothing(runs):
    """A program over one device has no boundary to count at (its text is
    pinned in test_one_program.py); at `rehearse_rows` no table of query
    7 (65,536 slots at most) is larger than the capacity hint's 262,144,
    so no aggregate is cut — but the hint's lower rung, 8,192 rows, is
    narrower than both aggregates' 65,536-row inputs (the upper one,
    65,536, is not), so each chooses between the two widths, and the few
    dozen live rows take the rung.  Two aggregates, two bodies each: four
    derivations of segment bounds for thirty-two reductions.  The four
    joins are a chain over store_sales' scan: the few hundred rows the
    first one keeps run the three later ones at the rung of 1,024."""
    _cat, _params, session, plan, _got = runs[SEEDS[0]]
    with config.conf.scoped({"auron.trace.enable": True}):
        one = session.execute(plan, mesh=data_mesh(1))
    assert sorted(one.stage_stats) == ["agg_inputs", "ingest", "join_chains",
                                       "join_probes", "segments", "shard"]
    aggs = one.stage_stats["agg_inputs"]
    assert sorted(aggs) == ["agg#1", "agg#3"]
    for a in aggs.values():
        assert (a["input"], a["rows"], a["capacity"], a["cap"]) == \
            ("compact", 8192, 65536, 262144) and 0 < a["live"] <= 8192
    assert one.stage_stats["segments"] == {"bounds": 4, "reductions": 32}
    [(label, chain)] = one.stage_stats["join_chains"].items()
    assert (label, chain["chain"], chain["rows"], chain["capacity"]) == \
        ("broadcast_join#11", "compact", 1024, 65536)
    # the driver's own count of what the scan leaves' tasks read (PR 31)
    assert one.stage_stats["ingest"]["scans"] == 5
    assert one.stage_stats["ingest"]["device_batches"] == 0
    totals = tracing.find_query(one.query_id).metric_totals
    assert not set(_TOTALS) & set(totals)
    assert not set(_TOTALS) & set(_wait_args(one))
    assert totals["join_probes_direct"] == 4
    assert (totals["segment_bounds"], totals["segment_reductions"]) == (4, 32)
    assert _wait_args(one)["segment_bounds"] == 4
    for where in (totals, _wait_args(one)):
        assert (where["agg_inputs"], where["agg_inputs_compact"],
                where["agg_inputs_below_cap"]) == (2, 2, 2)
    shard = [s.args for s in one.trace.snapshot() if s.name == "spmd.shard"]
    # what `_DEVICE_SHARDS` did for the attempt (PR 33): each source
    # served or placed (shards placed for four devices serve no other
    # mesh; an earlier one-device run's do), nothing evicted, nothing
    # past the budget
    [shard] = shard
    assert shard == {"sources": 5, "cached": shard["cached"],
                     "placed": 5 - shard["cached"],
                     "shard_put_bytes": shard["shard_put_bytes"],
                     "evicted": 0,
                     "held_bytes": shard["held_bytes"],
                     "over_budget_bytes": 0}
    assert shard["cached"] in (0, 5)
    assert (shard["shard_put_bytes"] > 0) == (shard["placed"] > 0)
    assert shard["held_bytes"] > 0
    assert one.stage_stats["shard"] == {
        k: shard[k] for k in S.SHARD_COUNTS}
    text = one.explain_analyze()
    assert " slots=" not in text and " quota=" not in text


def test_the_cell_differs_from_its_control_by_the_mesh_alone(cell):
    """Same tables, query, traffic, reference and limits as
    `tpcds-sf1.q07`; `chips` and `mesh_devices` 4."""
    control = cells.load_cell("tpcds-sf1.q07")
    assert cell.traffic == control.traffic and cell.query is control.query
    same = ("rows", "rehearse_rows", "data_seed", "scale_factor",
            "source_scale_factor", "reduced", "seeds")
    assert {k: cell.config[k] for k in same} == \
        {k: control.config[k] for k in same}
    assert cell.config["guarantees"].items() >= \
        control.config["guarantees"].items()
    assert control.config["assumed"] == \
        cell.config["assumed"][:len(control.config["assumed"])]
    assert (control.chips, control.config["mesh_devices"]) == (1, 1)
    with open(os.path.join(cells.REPO_DIR, "BENCHMARK.json")) as f:
        bench = json.load(f)
    [entry] = [c for c in bench["configs"] if c["name"] == "tpcds-sf1-4chip"]
    assert entry["source"] == cell.config["source"]
    assert entry["reduced"] == cell.config["reduced"] == ["scale_factor"]

"""The benchmark's configuration `tpcds-sf10-1chip` (PR 33) at its
`rehearse_rows`, through `AuronSession.execute` with no option set: the
reference's answer, the program the SF1 configuration runs, and both source
caches serving every source from the second execute on."""

import hashlib
import json
import os

import pytest

from auron_tpu import config
from auron_tpu.frontend.session import AuronSession
from auron_tpu.it.oracle import PyArrowEngine
from auron_tpu.parallel import stage as S
from auron_tpu.parallel.mesh import data_mesh
from auron_tpu.runtime import tracing
from benchmarks.harness import cells, compare, datagen
from benchmarks.queries import q07

CELL = "tpcds-sf10.q07"
SF1_CELL = "tpcds-sf1.q07"
SEEDS = (7, 2**31 + 13)
TABLES = ("store_sales", "customer_demographics", "date_dim", "item",
          "promotion")


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


@pytest.fixture(scope="module")
def runs(cell, tmp_path_factory):
    """seed -> (catalog, parameters, three traced executes of one warm
    session, from cold source caches)."""
    params = cell.traffic["param_sets"][0]
    out = {}
    for seed in SEEDS:
        cat = datagen.generate(
            str(tmp_path_factory.mktemp(f"sf10-q07-{seed}")), q07.SCANS,
            cell.config["rehearse_rows"], cell.config["data_seed"], seed)
        session = AuronSession(foreign_engine=PyArrowEngine())
        plan = q07.build_plan(cat, params)
        S.clear_source_caches()
        with config.conf.scoped({"auron.trace.enable": True}):
            got = [session.execute(plan) for _ in range(3)]
        out[seed] = (cat, params, got)
    return out


def _span_args(res, name):
    [span] = [s for s in res.trace.snapshot() if s.name == name]
    return span.args


# -- (i) the configuration --------------------------------------------------

def test_the_configuration_is_table_3_2s_sf10_column(cell):
    cfg = cell.config
    assert cfg["rows"] == {
        "store_sales": 28_800_991, "customer_demographics": 1_920_800,
        "date_dim": 73_049, "item": 102_000, "promotion": 500,
        "customer": 500_000, "customer_address": 250_000, "store": 102}
    assert (cfg["scale_factor"], cfg["source_scale_factor"]) == (10, 1000)
    assert cfg["reduced"] == ["scale_factor"] == list(cfg["reduced_why"])
    assert (cell.chips, cfg["chips"], cfg["mesh_devices"]) == (1, 1, 1)
    # everything but the row counts is the SF1 configuration's
    sf1 = cells.load_cell(SF1_CELL)
    assert cfg["data_seed"] == sf1.config["data_seed"]
    assert cfg["guarantees"] == sf1.config["guarantees"]
    assert cfg["deployment"] == sf1.config["deployment"]
    assert cell.traffic == sf1.traffic and cell.query is sf1.query
    assert sorted(m["name"] for m in cell.per_layer) == \
        sorted([m["name"] for m in sf1.per_layer] + ["stage.shard_ms"])
    assert cells.metric_spec("stage.shard_ms") | {"note": ""} == {
        "source": "span", "span": "spmd.shard", "note": ""}


@pytest.mark.parametrize("table", TABLES)
def test_the_rehearsal_has_sf10s_shape_not_sf1s(cell, table):
    """SF10's ratio of the fact table to each dimension that scales, at
    about the SF1 file's rehearsal size; date_dim whole."""
    rows, small = cell.config["rows"], cell.config["rehearse_rows"]
    sf1_small = cells.load_cell(SF1_CELL).config["rehearse_rows"]
    assert set(small) == set(rows)
    if table == "store_sales":
        assert 0.8 <= small[table] / sf1_small[table] <= 1.25
    elif table == "date_dim":
        assert small[table] == rows[table]
    elif table == "promotion":
        # 1/600 of it is under one row: the SF1 rehearsal's, times 500/300
        assert small[table] == sf1_small[table] * rows[table] // 300
    else:
        ratio = rows["store_sales"] / rows[table]
        assert small["store_sales"] / small[table] == \
            pytest.approx(ratio, rel=0.01)


# -- (ii) the answer is the reference's -------------------------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_the_answer_is_the_references(runs, seed):
    cat, params, got = runs[seed]
    want = q07.reference(cat.read, params)
    assert want.num_rows > 0
    for res in got:
        assert res.spmd and res.spmd_rejection is None
        verdict = compare.judge(compare.compare_tables(res.table, want),
                                q07.LIMITS)
        assert verdict["ok"], verdict
    warm = tracing.find_query(got[-1].query_id)
    assert (warm.retries, warm.fallbacks) == (0, 0)
    assert warm.metric_totals.get("num_fallbacks", 0) == 0
    assert warm.metric_totals["join_probes_direct"] == 4


# -- (iii) the program is the SF1 configuration's ---------------------------

def _lowered(cell, rows, tmp):
    from stage_spy import spied_program
    from auron_tpu.frontend import converters, strategy
    from auron_tpu.frontend.converters import ConvertContext
    cat = datagen.generate(str(tmp), q07.SCANS, rows,
                           cell.config["data_seed"], 5)
    plan = q07.build_plan(cat, cell.traffic["param_sets"][0])
    ctx = ConvertContext()
    converted = converters.convert_recursively(plan, strategy.apply(plan),
                                               ctx)
    program, inputs = spied_program(converted, ctx, data_mesh(1), {})
    return program.lower(inputs).as_text(), converted, ctx


def test_at_equal_shapes_it_is_the_sf1_configurations_program(cell,
                                                              tmp_path):
    """The new cell's configuration, traffic and query at the SF1 file's
    `rehearse_rows` lower to the text `test_one_program.py` pins for the
    SF1 cell: no kernel, operator or plan shape is SF10's own."""
    from test_one_program import CHIP_PROGRAM
    rows = cells.load_cell(SF1_CELL).config["rehearse_rows"]
    text, _plan, _ctx = _lowered(cell, rows, tmp_path)
    assert hashlib.sha256(text.encode()).hexdigest() == \
        CHIP_PROGRAM["q07-one-device"]


def test_at_its_own_shapes_it_is_the_same_operators(cell, tmp_path):
    """At its own `rehearse_rows` the stage plan has the SF1 cell's
    operators under the SF1 cell's labels; only buffer sizes differ."""
    sf1 = cells.load_cell(SF1_CELL)
    labels = []
    for c, d in ((cell, "sf10"), (sf1, "sf1")):
        _text, plan, ctx = _lowered(c, c.config["rehearse_rows"],
                                    tmp_path / d)
        labels.append([(depth, label) for depth, _node, label
                       in S.operator_labels(plan, ctx)])
    assert labels[0] == labels[1] and len(labels[0]) > 10


# -- (iv) every source cached from the second execute on --------------------

@pytest.mark.parametrize("seed", SEEDS)
def test_from_the_second_execute_on_nothing_is_read_or_placed(runs, seed):
    cat, _params, got = runs[seed]
    first = _span_args(got[0], "spmd.ingest")
    # a task a file: store_sales' four, customer_demographics' two
    assert (first["scans"], first["cached"], first["tasks"]) == (
        5, 0, sum(len(cat.tables[t].chunks) for t in TABLES)) == (5, 0, 9)
    assert first["rows"] == sum(cat.tables[t].rows for t in TABLES)
    assert _span_args(got[0], "spmd.shard")["placed"] == 5
    arrow_bytes = first["held_bytes"]
    device_bytes = _span_args(got[0], "spmd.shard")["held_bytes"]
    assert _span_args(got[0], "spmd.shard")["shard_put_bytes"] == \
        device_bytes
    assert arrow_bytes == first["bytes"] > 0 and device_bytes > 0
    for res in got[1:]:
        ingest = _span_args(res, "spmd.ingest")
        assert {k: ingest[k] for k in S.INGEST_COUNTS + S.CACHE_STATE} == {
            "scans": 5, "cached": 5, "tasks": 0, "batches": 0, "rows": 0,
            "bytes": 0, "device_batches": 0, "evicted": 0,
            "held_bytes": arrow_bytes,
            # as reckoned: at the rehearsal's 5 MB against a budget of
            # 2,048 nothing is over (on the chip: store_sales' 2,448 MB)
            "over_budget_bytes": 0}
        shard = _span_args(res, "spmd.shard")
        assert {k: shard[k] for k in S.SHARD_COUNTS} == {
            "cached": 5, "placed": 0, "shard_put_bytes": 0, "evicted": 0,
            "held_bytes": device_bytes, "over_budget_bytes": 0}
        names = [s.name for s in res.trace.snapshot()]
        assert "shard.pad" not in names and "shard.put" not in names
        assert "scan.decode" not in names
        totals = tracing.find_query(res.query_id).metric_totals
        assert {k: totals[k] for k in (
            "scan_cached", "shards_cached", "source_evictions",
            "source_over_budget_bytes", "scan_rows")} == {
            "scan_cached": 5, "shards_cached": 5, "source_evictions": 0,
            "source_over_budget_bytes": 0, "scan_rows": 0}
        assert res.stage_totals()["shards_cached"] == 5


def test_at_sf10_the_fact_table_alone_is_over_the_scan_caches_budget(cell):
    """The reckoning behind the rule: store_sales' scanned columns are 85
    B a row of Arrow, over `auron.spmd.scan.cache.mb` by itself at SF10
    and a ninth of it at SF1; the working set at SF1 is under every
    budget."""
    budget = S._SCAN_TABLES._budget()
    assert budget == 2048 << 20 and S._DEVICE_SHARDS._budget() == 4096 << 20
    per_row = 4 * 8 + 4 + 3 * 16 + 8 / 8      # keys, quantity, money, masks
    rows = cell.config["rows"]["store_sales"]
    assert per_row * rows > budget
    sf1_rows = cells.load_cell(SF1_CELL).config["rows"]["store_sales"]
    assert 8 * per_row * sf1_rows + (86 << 20) < budget < \
        9 * per_row * sf1_rows


def test_the_benchmark_gained_one_configuration_one_cell_one_metric():
    with open(os.path.join(cells.REPO_DIR, "BENCHMARK.json")) as f:
        bench = json.load(f)
    # found by name: later PRs append their own entries after these
    assert [c["name"] for c in bench["configs"]].count(
        "tpcds-sf10-1chip") == 1
    [workload] = [w for w in bench["workloads"] if w["name"] == CELL]
    assert workload == {
        "name": CELL, "config": "tpcds-sf10-1chip",
        "traffic": "q07-cached-loop", "chips": 1,
        "why": workload["why"]}
    [metric] = [m for m in bench["per_layer"]
                if m["name"] == "stage.shard_ms"]
    assert metric == {
        "name": "stage.shard_ms", "unit": "ms", "better": "lower",
        "source": "program_span", "layer": "stage driver",
        "moves": "query_s", "workloads": [CELL]}
    assert sum(w["config"] == "tpcds-sf10-1chip"
               for w in bench["workloads"]) == 1

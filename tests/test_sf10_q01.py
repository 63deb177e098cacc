"""The benchmark's configuration `tpcds-sf10-returns-1chip` and its cell
`tpcds-sf10.q01` (PR 35) at `rehearse_rows`, through
`AuronSession.execute` with no option set: TPC-DS query 1 with spec-typed
money as ONE stage program — the reference's answer to the seventh place
of the thresholds, the capacity ladder's climb where the first aggregate's
groups pass the hint, and what the program says of itself."""

import json
import os
from decimal import Decimal

import numpy as np
import pytest

from auron_tpu import config
from auron_tpu.frontend import converters, strategy
from auron_tpu.frontend.converters import ConvertContext
from auron_tpu.frontend.session import AuronSession
from auron_tpu.it.oracle import PyArrowEngine
from auron_tpu.parallel import stage as S
from auron_tpu.runtime import tracing
from benchmarks.harness import cells, compare, datagen, refmath
from benchmarks.harness.plans import (I64, DataType, Field, Schema, agg,
                                      falias, fcall, fcol, fproject,
                                      two_phase_agg)
from benchmarks.queries import q01

CELL = "tpcds-sf10.q01"
SF10_Q07 = "tpcds-sf10.q07"
SEEDS = (7, 2**31 + 13, 2147483647)
SETS = ("template", "projecting")


@pytest.fixture(scope="module")
def cell():
    return cells.load_cell(CELL)


def _catalog(cell, tmp, seed):
    return datagen.generate(str(tmp), q01.SCANS,
                            cell.config["rehearse_rows"],
                            cell.config["data_seed"], seed)


@pytest.fixture(scope="module")
def runs(cell, tmp_path_factory):
    """(seed, parameter set) -> (catalog, parameters, two traced executes
    of one warm session)."""
    out = {}
    session = AuronSession(foreign_engine=PyArrowEngine())
    for seed in SEEDS:
        cat = _catalog(cell, tmp_path_factory.mktemp(f"sf10-q01-{seed}"),
                       seed)
        for name, params in zip(SETS, cell.traffic["param_sets"]):
            plan = q01.build_plan(cat, params)
            with config.conf.scoped({"auron.trace.enable": True}):
                got = [session.execute(plan) for _ in range(2)]
            out[seed, name] = (cat, params, got)
    return out


def _spans(res, name):
    return [s for s in res.trace.snapshot() if s.name == name]


# -- (i) the configuration and the cell ----------------------------------------

def test_the_configuration_is_table_3_2s_sf10_column(cell):
    cfg = cell.config
    assert cfg["rows"] == {
        "store_returns": 2_875_432, "date_dim": 73_049, "store": 102,
        "customer": 500_000, "store_sales": 28_800_991, "item": 102_000,
        "customer_demographics": 1_920_800, "customer_address": 250_000}
    # the database the SF10 cell of query 7 runs at
    q07 = cells.load_cell(SF10_Q07).config
    assert {t: n for t, n in cfg["rows"].items() if t in q07["rows"]} == \
        {t: n for t, n in q07["rows"].items() if t in cfg["rows"]}
    assert (cfg["scale_factor"], cfg["source_scale_factor"]) == (10, 1000)
    assert cfg["data_seed"] == q07["data_seed"] == 77
    assert cfg["deployment"] == q07["deployment"]
    assert cfg["reduced"] == ["scale_factor"] == list(cfg["reduced_why"])
    assert (cell.chips, cfg["chips"], cfg["mesh_devices"]) == (1, 1, 1)
    assert set(cfg["rehearse_rows"]) == set(cfg["rows"])
    assert "no double" in cfg["guarantees"]["doubles"] or \
        cfg["guarantees"]["doubles"].startswith("none")
    assert cell.query is q01 and set(q01.SCANS) <= set(cfg["rows"])


def test_the_traffic_is_the_template_and_the_projecting_set(cell):
    template, projecting = cell.traffic["param_sets"]
    same = ("YEAR", "STATE", "AGG_FIELD")
    assert [template[k] for k in same] == [projecting[k] for k in same] == \
        [2000, "TN", "SR_RETURN_AMT"]
    assert q01.select_list(template) == ["c_customer_id"]
    assert q01.select_list(projecting) == [
        "c_customer_id", "ctr_store_sk", "ctr_total_return",
        "ctr_threshold"]
    assert cell.traffic["replaced_before_each_execute"] == []
    assert q01.LIMITS == {"rows_differ": 0, "float_rel_gap": 1e-10}


def test_the_benchmark_gained_one_configuration_and_one_cell():
    with open(os.path.join(cells.REPO_DIR, "BENCHMARK.json")) as f:
        bench = json.load(f)
    [entry] = [c for c in bench["configs"]
               if c["name"] == "tpcds-sf10-returns-1chip"]
    assert entry["file"] == \
        "benchmarks/configs/tpcds-sf10-returns-1chip.json"
    assert entry["source"] == cells.load_cell(CELL).config["source"]
    assert entry["reduced"] == ["scale_factor"]
    [workload] = [w for w in bench["workloads"] if w["name"] == CELL]
    assert workload == {
        "name": CELL, "config": "tpcds-sf10-returns-1chip",
        "traffic": "q01-cached-loop", "chips": 1, "why": workload["why"]}
    assert sum(w["config"] == "tpcds-sf10-returns-1chip"
               for w in bench["workloads"]) == 1
    # every metric the cell reads was there before it
    assert all(CELL not in m.get("workloads", []) or
               m["name"].startswith("dec128.") for m in bench["per_layer"])


# -- (ii) the tables ---------------------------------------------------------

def test_the_tables_are_the_deployments(cell, tmp_path):
    cat = _catalog(cell, tmp_path, 11)
    rows = cell.config["rehearse_rows"]
    sr = cat.read("store_returns", q01.SCANS["store_returns"])
    assert sr.num_rows == rows["store_returns"]
    for name in sr.schema.names:       # 4.5 % nulls a nullable column
        assert 0.03 < sr[name].null_count / sr.num_rows < 0.06, name
    assert str(sr.schema.field("sr_return_amt").type) == "decimal128(7, 2)"
    dd = cat.read("date_dim", q01.SCANS["date_dim"]).to_pandas()
    days = dd[dd.d_year == 2000].d_date_sk.to_numpy()
    share = np.isin(refmath.ints(sr["sr_returned_date_sk"]), days).mean()
    assert 0.15 < share < 0.25         # five years of sales, one of them
    st = cat.read("store", q01.SCANS["store"]).to_pandas()
    assert 0 < (st.s_state == "TN").sum() < len(st) == rows["store"]
    cu = cat.read("customer", q01.SCANS["customer"])
    assert cu["c_customer_sk"].to_pylist()[:2] == [1, 2]
    assert cu["c_customer_id"].to_pylist()[:2] == [
        "AAAAAAAABAAAAAAA", "AAAAAAAACAAAAAAA"]
    assert len(set(cu["c_customer_id"].to_pylist())) == rows["customer"]


def test_a_seed_moves_the_amounts_and_nothing_else(cell, tmp_path):
    a = _catalog(cell, tmp_path / "a", 1).read(
        "store_returns", q01.SCANS["store_returns"])
    b = _catalog(cell, tmp_path / "b", 2).read(
        "store_returns", q01.SCANS["store_returns"])
    for name in ("sr_returned_date_sk", "sr_customer_sk", "sr_store_sk"):
        assert a[name].equals(b[name])
    assert not a["sr_return_amt"].equals(b["sr_return_amt"])
    assert a["sr_return_amt"].null_count == b["sr_return_amt"].null_count


# -- (iii) the answer is the reference's, from one stage program ------------

@pytest.mark.parametrize("which", SETS)
@pytest.mark.parametrize("seed", SEEDS)
def test_the_answer_is_the_references(runs, seed, which):
    cat, params, got = runs[seed, which]
    want = q01.reference(cat.read, params)
    assert want.num_rows == 100
    for res in got:
        assert res.spmd and res.spmd_rejection is None
        verdict = compare.judge(compare.compare_tables(res.table, want),
                                q01.LIMITS)
        assert verdict["ok"], verdict
    warm = tracing.find_query(got[-1].query_id)
    assert (warm.retries, warm.fallbacks) == (0, 0)
    assert warm.metric_totals.get("num_fallbacks", 0) == 0
    assert warm.metric_totals["wide_decimal_columns"] > 0
    # the date and store lookups of both copies of the CTE, the threshold's
    # and customer's: every join probes by direct address
    assert warm.metric_totals["join_probes"] == \
        warm.metric_totals["join_probes_direct"] == 5
    if which == "projecting":
        assert str(res.table.schema.field("ctr_threshold").type) == \
            "decimal128(24, 7)"
        assert str(res.table.schema.field("ctr_total_return").type) == \
            "decimal128(17, 2)"
        totals = res.table["ctr_total_return"].to_pylist()
        thresholds = res.table["ctr_threshold"].to_pylist()
        assert all(t > th for t, th in zip(totals, thresholds))
        assert len(set(thresholds)) > 1 and all(
            th == th.quantize(Decimal("1e-7")) for th in thresholds)


def test_the_threshold_is_sparks_two_roundings():
    """The reference's own arithmetic, on numbers worked by hand: 1 cent
    over 3 groups is 0.003333 at the sixth place (0.0033333333333 at the
    thirteenth first), times 1.2 is 0.0039996; 2 cents over 3 round up."""
    assert q01.spark_threshold(1, 3) == 39996
    assert q01.spark_threshold(2, 3) == 80004        # 0.006667 * 1.2
    assert q01.spark_threshold(-2, 3) == -80004
    assert q01.spark_threshold(10**15, 7) == \
        round(Decimal(10**13) / 7, 6).scaleb(6) * 12 // 1


def test_the_program_says_what_it_held(runs):
    _cat, _params, got = runs[SEEDS[0], "projecting"]
    res = got[-1]
    text = res.explain_analyze()
    lines = {ln.strip().split(" ")[0]: ln for ln in text.splitlines()
             if "#" in ln}
    averaged = [ln for label, ln in lines.items()
                if label.startswith("agg#") and "dec128=" in ln]
    assert len(averaged) == 2          # the average's partial and final
    assert any("dec128=" in ln for label, ln in lines.items()
               if label.startswith("broadcast_join#"))
    assert "fallbacks=0" in text and "mode=spmd" in text
    [wait] = _spans(res, "spmd.wait")
    assert wait.args["wide_decimal_columns"] == \
        res.stage_totals()["wide_decimal_columns"] == \
        sum(res.stage_stats["wide_columns"].values())
    assert len(_spans(res, "spmd.run")) == 1 and not _spans(
        res, "spmd.compile")


# -- (iv) the capacity ladder ---------------------------------------------------

def test_past_the_capacity_hint_the_first_execute_climbs_and_the_second_knows(
        cell, tmp_path):
    """With `auron.spmd.agg.capacity.hint` scoped under the (customer,
    store) groups of year 2000 — as SF10's 575,000 lie over the default
    262,144 — the first execute trips the shrink guard, discards, climbs
    one rung (x4) and compiles again; the second finds the rung learned:
    no retry, no compile."""
    cat = _catalog(cell, tmp_path, 3)
    params = cell.traffic["param_sets"][1]
    want = q01.reference(cat.read, params)
    session = AuronSession(foreign_engine=PyArrowEngine())
    plan = q01.build_plan(cat, params)
    S._SHRINK_HINT.clear()
    with config.conf.scoped({"auron.trace.enable": True,
                             "auron.spmd.agg.capacity.hint": 1024}):
        first, second = session.execute(plan), session.execute(plan)
    for res in (first, second):
        assert res.spmd and compare.judge(
            compare.compare_tables(res.table, want), q01.LIMITS)["ok"]
    one, two = (tracing.find_query(r.query_id) for r in (first, second))
    assert (one.retries, one.fallbacks) == (1, 0)
    assert (two.retries, two.fallbacks) == (0, 0)
    assert len(_spans(first, "spmd.compile")) == 2
    assert not _spans(second, "spmd.compile") and \
        len(_spans(second, "spmd.run")) == 1
    # what the program says: the rung, and which side each aggregate took
    aggs = second.stage_stats["agg_inputs"]
    assert {a["cap"] for a in aggs.values()} == {4096}
    assert two.metric_totals["agg_capacity"] == 4096
    cut = [a for a in aggs.values() if a["capacity"] == 16384]
    assert cut and all(a["input"] == "compact" and a["rows"] == 4096
                       and 1024 < a["live"] <= 4096 for a in cut)
    # the rung has a rung of its own, 1,024 rows, so the aggregates over
    # the 4,096-row tables choose too: the average by store alone has few
    # enough rows for it (as the cell's `agg#28` alone takes 32,768 under
    # 1,048,576), the three others run at their input's width
    uncut = [a for a in aggs.values() if a["capacity"] == 4096]
    assert sorted(a["rows"] for a in uncut) == [1024, 4096, 4096, 4096]
    assert [a["input"] for a in uncut if a["rows"] == 1024] == ["compact"]
    assert two.metric_totals["agg_inputs_below_cap"] == 1
    text = second.explain_analyze()
    assert text.count(" cap=4096") == len(aggs) >= 2
    # the attempt that tripped worked on the full table: more live rows
    # than its rung holds
    tripped = first.stage_stats["agg_inputs"]
    assert {a["cap"] for a in tripped.values()} == {4096}


def test_over_four_virtual_devices_it_is_still_one_program(cell, tmp_path):
    """Not the cell's layout (one chip), but the mechanism's reach: both
    words of the averages' buffers cross the exchange's `all_to_all`, the
    thresholds the broadcast's `all_gather`, and the answer is the
    reference's."""
    from auron_tpu.parallel.mesh import data_mesh
    cat = _catalog(cell, tmp_path, 9)
    params = cell.traffic["param_sets"][1]
    res = AuronSession(foreign_engine=PyArrowEngine()).execute(
        q01.build_plan(cat, params), mesh=data_mesh(4))
    assert res.spmd and res.spmd_rejection is None
    assert compare.judge(compare.compare_tables(
        res.table, q01.reference(cat.read, params)), q01.LIMITS)["ok"]
    totals = res.stage_totals()
    assert totals["wide_decimal_columns"] > 0
    assert totals["exchange_rows_moved"] > 0 and totals["broadcast_rows"] > 0
    wide = res.stage_stats["wide_columns"]
    assert any(label in wide for label in res.stage_stats["exchanges"])
    assert any(label in wide for label in res.stage_stats["broadcasts"])


# -- (v) what stays out -----------------------------------------------------

def test_a_wide_decimal_as_a_group_key_is_refused_with_its_reason(
        cell, tmp_path):
    """By `iter_spmd_rejections`, on the converted plan, before a source
    is read; the session then answers from the serial engine, whose host
    path holds the type."""
    cat = _catalog(cell, tmp_path, 5)
    wide = DataType.decimal(24, 7)
    sr = cat.scan("store_returns", q01.SCANS["store_returns"])
    keyed = fproject(
        sr, [falias(fcall("Cast", fcol("sr_return_amt", q01.MONEY),
                          dtype=wide), "amt")],
        Schema((Field("amt", wide),)))
    plan = two_phase_agg(
        keyed, grouping=[fcol("amt", wide)], group_fields=[Field("amt", wide)],
        aggs=[("n", agg("Count", fcol("amt", wide), I64), Field("n", I64))])
    ctx = ConvertContext()
    converted = converters.convert_recursively(plan, strategy.apply(plan),
                                               ctx)
    reasons = [r for _n, r in S.iter_spmd_rejections(converted, ctx)]
    assert "a wide decimal (decimal(24,7)) as group key" in reasons
    assert "a wide decimal (decimal(24,7)) as exchange key" in reasons
    res = AuronSession(foreign_engine=PyArrowEngine()).execute(plan)
    assert not res.spmd and "a wide decimal" in res.spmd_rejection
    amounts = cat.read("store_returns", ["sr_return_amt"])["sr_return_amt"]
    assert res.table.num_rows == len(set(amounts.to_pylist()))

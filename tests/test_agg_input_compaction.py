"""An aggregate's input, compacted to the capacity its output is cut to
anyway (`_StageTracer._do_agg`): the choice the stage program makes from
the live count, on both of its sides and on inputs that leave no choice —
against the serial engine, row for row; what such a program lowers to; and
where its counter goes."""

from decimal import Decimal

import numpy as np
import pyarrow as pa
import pytest

from auron_tpu.config import conf
from auron_tpu.frontend.converters import ShuffleJob
from auron_tpu.ir import expr as E
from auron_tpu.ir import plan as P
from auron_tpu.ir.expr import AggExpr, col, lit
from auron_tpu.ir.schema import DataType, from_arrow_schema
from auron_tpu.parallel import stage as S
from auron_tpu.parallel.mesh import data_mesh
from auron_tpu.runtime import retry, tracing
from test_spmd_stage import _Ctx, _canon, _serial_reference

I64 = DataType.int64()
F64 = DataType.float64()
DEC_IN = DataType.decimal(7, 2)
DEC_SUM = DataType.decimal(17, 2)

ROWS = 6000
TARGET = 1024           # the scoped-down capacity hint: its own bucket
HINT = {"auron.spmd.agg.capacity.hint": TARGET}


def _fact(live: str) -> pa.Table:
    """6,000 rows in 8,192 slots on one device, 1,500 in 2,048 on each of
    four.  `pick` < 0.5 marks the rows the plans keep:

    - `few`: 300 rows, under the target on every device;
    - `most`: every row, over it on every device, in 40 groups;
    - `groups`: every row a group of its own, over the target in groups;
    - `one-device`: the first 1,500 rows (device 0's, of four) and 75 of
      each later 1,500;
    - `none`: no row."""
    rng = np.random.default_rng(11)
    seq = np.arange(ROWS, dtype=np.int64)
    pick = {
        "few": np.where(seq % 20 == 0, 0.0, 1.0),
        "most": np.zeros(ROWS),
        "groups": np.zeros(ROWS),
        "one-device": np.where((seq < 1500) | (seq % 20 == 0), 0.0, 1.0),
        "none": np.ones(ROWS),
    }[live]
    key = seq if live == "groups" else rng.integers(0, 40, ROWS)
    cents = rng.integers(-99999, 99999, ROWS)
    return pa.table({
        "seq": seq,
        "pick": pick,
        "key": key.astype(np.int64),
        "name": pa.array([f"name-{k:05d}" for k in key]),
        "amount": rng.normal(10, 30, ROWS),
        "price": pa.array(
            [None if i % 7 == 3 else Decimal(int(c)) / 100
             for i, c in enumerate(cents)], type=pa.decimal128(7, 2)),
    })


_AGGS = {
    # shape -> (grouping column, its type, aggregates)
    "sums": ("key", (
        AggExpr(fn="sum", children=(col("amount"),), return_type=F64),
        AggExpr(fn="count", children=(col("amount"),), return_type=I64),
        AggExpr(fn="avg", children=(col("amount"),), return_type=F64))),
    "string-key": ("name", (
        AggExpr(fn="max", children=(col("seq"),), return_type=I64),)),
    "decimal-sum": ("key", (
        AggExpr(fn="sum", children=(col("price"),), return_type=DEC_SUM),
        AggExpr(fn="count", children=(col("price"),), return_type=I64))),
    # the first `seq` of a group is its earliest row: a state that reads
    # the rows' order
    "first": ("key", (
        AggExpr(fn="first", children=(col("seq"),), return_type=I64),
        AggExpr(fn="first_ignores_null", children=(col("price"),),
                return_type=DEC_IN))),
    "global": (None, (
        AggExpr(fn="count", children=(col("amount"),), return_type=I64),
        AggExpr(fn="sum", children=(col("amount"),), return_type=F64))),
}


def _plans(fact, shape, mode):
    """(stage plan, ctx, serial plan) of one aggregate shape over the
    picked rows: `partial` -> hash exchange -> `final`, or a hash exchange
    -> `single` (a global aggregate funnels through a single exchange)."""
    by, aggs = _AGGS[shape]
    names = tuple(f"a{i}" for i in range(len(aggs)))
    agg = dict(grouping=(col(by),) if by else (),
               grouping_names=(by,) if by else (),
               aggs=aggs, agg_names=names)
    src = P.Filter(
        child=P.FFIReader(schema=from_arrow_schema(fact.schema),
                          resource_id="fact"),
        predicates=(E.BinaryExpr(left=col("pick"), op="<",
                                 right=lit(0.5)),))
    part = P.Partitioning(mode="hash", num_partitions=8,
                          expressions=(col(by),)) if by else \
        P.Partitioning(mode="single", num_partitions=1)
    ctx = _Ctx()
    below = src if mode == "single" else \
        P.Agg(child=src, exec_mode="partial", **agg)
    ctx.exchanges["ex"] = ShuffleJob(rid="ex", child=below,
                                     partitioning=part, schema=None)
    top = "single" if mode == "single" else "final"
    stage = P.Agg(child=P.IpcReader(schema=None, resource_id="ex"),
                  exec_mode=top, **agg)
    serial = P.Agg(child=below, exec_mode=top, **agg)
    return stage, ctx, serial


def _run(shape, mode, live, n_dev):
    """One run against the serial engine: (the input each aggregate that
    chose worked on, in trace order — the deepest first; retries)."""
    fact = _fact(live)
    stage, ctx, serial = _plans(fact, shape, mode)
    S._SHRINK_HINT.clear()
    stats = {}
    before = retry.stats_snapshot()["retries"]
    with conf.scoped(HINT):
        got = S.execute_plan_spmd(stage, ctx, data_mesh(n_dev),
                                  {"fact": fact}, stats=stats)
    want = _serial_reference(serial, {"fact": fact})
    assert got.num_rows == len(want) > 0
    assert _canon(got.to_pylist()) == _canon(want)
    marks = [a["input"] for a in stats["agg_inputs"].values()]
    return marks, retry.stats_snapshot()["retries"] - before, stats


# On one device the exchange is an identity: the first aggregate alone sees
# a table larger than the target.  On four the exchange hands the final
# aggregate 4 x (2 x 1024 / 4 + 8) = 2,080 slots, so it chooses too (and
# its live rows, the partial aggregates' groups, fit the target).
@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("shape,mode", [
    ("sums", "partial"), ("sums", "single"), ("string-key", "partial"),
    ("decimal-sum", "partial"), ("first", "partial"), ("first", "single"),
])
@pytest.mark.parametrize("live,first_input", [
    ("few", "compact"),     # live rows under the target
    ("most", "full"),       # over it; 40 groups: the cut loses nothing
])
def test_both_sides_of_the_choice_give_the_serial_answer(
        shape, mode, live, first_input, n_dev):
    marks, retries, _stats = _run(shape, mode, live, n_dev)
    assert retries == 0
    chose = 1 if n_dev == 1 or mode == "single" else 2
    if (mode, live, n_dev) == ("single", "most", 4):
        # the exchange under a single-mode aggregate deals the 40 keys'
        # rows by hash: one device of the four is dealt under the target
        first_input = "compact 1/4"
    assert len(marks) == chose and marks[0] == first_input
    # the final aggregate of four devices merges at most 4 x 40 groups
    assert marks[1:] == ["compact"][:chose - 1]


@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("shape,mode", [
    ("sums", "partial"), ("sums", "single"), ("string-key", "partial"),
    ("first", "partial"),
])
def test_groups_past_the_target_trip_the_guard_and_climb_the_ladder(
        shape, mode, n_dev):
    """6,000 groups (1,500 a device): the full side's cut would lose rows,
    its guard trips, the driver retries four times wider — as before there
    was a choice; the answer comes from a rung that holds them."""
    marks, retries, _stats = _run(shape, mode, "groups", n_dev)
    # one device: 4,096 trips again, 16,384 is no cut; four: 4,096 holds
    assert retries == (2 if n_dev == 1 else 1)
    [(_key, rung)] = S._SHRINK_HINT.items()
    assert rung == TARGET * (16 if n_dev == 1 else 4)
    # the rung that answered is past every table of one device; on four
    # the exchange's 4 x (2 x 2048 / 4 + 8) = 4,128 slots are not, and
    # their 1,500 live rows fit it
    assert marks == ([] if n_dev == 1 else ["compact"])


@pytest.mark.parametrize("shape", ["sums", "string-key", "first"])
def test_devices_choose_for_their_own_rows(shape):
    """Device 0 keeps all of its 1,500 rows, the three others 75 each:
    one runs the body at 2,048 rows, three at 1,024, the answer is one."""
    marks, retries, stats = _run(shape, "partial", "one-device", 4)
    assert retries == 0 and marks == ["compact 3/4", "compact"]
    first = next(iter(stats["agg_inputs"].values()))
    assert first["live"] == 1500 + 3 * 75 and first["capacity"] == 4 * 2048
    assert S.agg_input_counts(stats["agg_inputs"]) == \
        {"agg_inputs": 2, "agg_inputs_compact": 1}


@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("mode", ["partial", "single"])
def test_a_global_aggregate_over_no_rows_keeps_its_identity_row(mode,
                                                                n_dev):
    """count 0, sum null, from the compact side: no live row is no more
    than the target."""
    marks, retries, _stats = _run("global", mode, "none", n_dev)
    assert retries == 0 and marks and set(marks) == {"compact"}


# -- inputs that leave no choice ---------------------------------------------

def _lowered(scope, n_dev=1):
    """The lowered stage program of the `sums` plan (partial aggregate,
    hash exchange, final aggregate; 300 live rows of 6,000)."""
    from stage_spy import spied_program
    fact = _fact("few")
    stage, ctx, _serial_plan = _plans(fact, "sums", "partial")
    with conf.scoped(scope):
        program, inputs = spied_program(stage, ctx, data_mesh(n_dev),
                                        {"fact": fact})
        return program.lower(inputs).as_text()


# (test_one_program.py pins the text of both: one program, neither cuts
# anything)
_NO_CHOICE_PROGRAM = {
    # the default hint, 262,144 rows: the 8,192-row input is no larger
    "input-within-target": {},
    # the shrink off: no cut, so nothing to compact to
    "shrink-off": {"auron.spmd.agg.capacity.hint": 0},
}


@pytest.mark.parametrize("case", sorted(_NO_CHOICE_PROGRAM))
def test_an_aggregate_with_no_larger_input_traces_no_choice(case):
    text = _lowered(_NO_CHOICE_PROGRAM[case])
    assert "stablehlo.case" not in text and "stablehlo.if" not in text
    assert _lowered(HINT).count("stablehlo.case") == 1


def test_the_choice_is_one_conditional_with_no_collective_inside():
    """The compact side gathers by a scattered permutation and sorts
    nothing the full side does not; the guard's and the counter's `psum`
    lie outside both."""
    import jax
    from stage_spy import spied_program
    from test_stage_tracing import _eqns
    fact = _fact("few")
    stage, ctx, _serial_plan = _plans(fact, "sums", "partial")
    with conf.scoped(HINT):
        program, inputs = spied_program(stage, ctx, data_mesh(4),
                                        {"fact": fact})
        found = list(_eqns(jax.make_jaxpr(program)(inputs).jaxpr))
    conds = [eqn for eqn, _inside in found if eqn.primitive.name == "cond"]
    assert len(conds) == 2            # the partial and the final aggregate
    inside = [eqn.primitive.name for eqn, where in found
              if any(name == "cond" for name, _i in where)]
    assert "cumsum" in inside and "scatter" in inside
    assert not {"psum", "psum2", "all_to_all", "all_gather", "pmax"} \
        & set(inside)


# -- the counter ----------------------------------------------------------------

def test_counter_in_the_record_the_span_and_explain_analyze(tmp_path):
    """`agg_inputs` / `agg_inputs_compact` in the query record's totals and
    on `spmd.wait`'s args; `input=compact live=<rows> of <capacity>` on the
    aggregate's line of EXPLAIN ANALYZE.  Beside them `segment_bounds`,
    one an aggregate body traced (both sides of a choice are), and
    `segment_reductions`, the reductions that took them."""
    from auron_tpu.frontend.session import AuronSession
    from auron_tpu.it import queries
    from auron_tpu.it.datagen import generate
    from auron_tpu.it.oracle import PyArrowEngine
    catalog = generate(str(tmp_path / "tpcds"), sf=0.002)
    session = AuronSession(foreign_engine=PyArrowEngine())
    plan = queries.build("q03", catalog)
    with conf.scoped({"auron.trace.enable": True, **HINT}):
        res = session.execute(plan)
    assert res.spmd
    aggs = res.stage_stats["agg_inputs"]
    assert aggs and all(a["input"] == "compact" and
                        a["live"] <= TARGET < a["capacity"]
                        for a in aggs.values())
    lines = {ln.split()[0]: ln for ln in res.explain_analyze().splitlines()
             if ln.strip().startswith("agg#")}
    for label, a in aggs.items():
        assert (f" input=compact live={a['live']} of {a['capacity']}"
                in lines[label])
    assert any("input=" not in ln for ln in lines.values())   # the final
    totals = tracing.find_query(res.query_id).metric_totals
    [wait] = [s for s in res.trace.snapshot() if s.name == "spmd.wait"]
    for where in (totals, wait.args, res.stage_totals()):
        assert where["agg_inputs"] == where["agg_inputs_compact"] \
            == len(aggs)
        assert where["segment_bounds"] == len(lines) + len(aggs)
        assert where["segment_reductions"] > where["segment_bounds"]
    assert res.stage_stats["segments"] == {
        "bounds": totals["segment_bounds"],
        "reductions": totals["segment_reductions"]}
    # at the default hint q03's tables are no larger than the target: the
    # counter is there and counts nothing
    with conf.scoped({"auron.trace.enable": True}):
        plain = session.execute(plan)
    assert plain.stage_stats["agg_inputs"] == {}
    assert "input=" not in plain.explain_analyze()
    plain_totals = tracing.find_query(plain.query_id).metric_totals
    assert plain_totals["agg_inputs"] == 0
    assert plain_totals["segment_bounds"] == len(lines)
    # the choice's untaken side held as many reductions as the taken one
    assert (totals["segment_reductions"] * len(lines)
            == plain_totals["segment_reductions"]
            * (len(lines) + len(aggs)))
    assert plain.table.equals(res.table)

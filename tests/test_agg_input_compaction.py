"""An aggregate's input, compacted to the narrowest width that holds its
live rows (`_StageTracer._do_agg`): the capacity its output is cut to
anyway, or a rung below it.  The choice the stage program makes from the
live count, on every one of its sides and on inputs that leave no choice —
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
DEC_WIDE_SUM = DataType.decimal(27, 2)      # two words a value on the device
DEC_WIDE_AVG = DataType.decimal(21, 6)

ROWS = 6000
TARGET = 1024           # the scoped-down capacity hint: its own bucket
HINT = {"auron.spmd.agg.capacity.hint": TARGET}
# a rung is a capacity bucket: at the default `auron.batch.capacity.min`,
# 1,024, the target has none below it; with buckets from 32 rows on its
# rungs are a fourth and a thirty-second of it, 256 and 32 rows
RUNGS = {"auron.batch.capacity.min": 32, **HINT}


def _fact(live: str) -> pa.Table:
    """6,000 rows in 8,192 slots on one device, 1,500 in 2,048 on each of
    four.  `pick` < 0.5 marks the rows the plans keep:

    - `few`: 300 rows, under the target on every device;
    - `most`: every row, over it on every device, in 40 groups;
    - `groups`: every row a group of its own, over the target in groups;
    - `one-device`: the first 1,500 rows (device 0's, of four) and 75 of
      each later 1,500;
    - `none`: no row;
    - `at-rung`: the first 256 rows of every 1,500 (1,024 in all: on one
      device exactly the target, on each of four exactly a rung);
    - `over-rung`: the first 257 of every 1,500, one row over both;
    - `tiny`: the first 8 of every 1,500: 32 on one device, a rung's
      worth;
    - `ladder`: all of device 0's 1,500 rows, 500 of device 1's, 100 of
      device 2's and 20 of device 3's: four devices, four widths."""
    rng = np.random.default_rng(11)
    seq = np.arange(ROWS, dtype=np.int64)
    pick = {
        "few": np.where(seq % 20 == 0, 0.0, 1.0),
        "most": np.zeros(ROWS),
        "groups": np.zeros(ROWS),
        "one-device": np.where((seq < 1500) | (seq % 20 == 0), 0.0, 1.0),
        "none": np.ones(ROWS),
        "at-rung": np.where(seq % 1500 < 256, 0.0, 1.0),
        "over-rung": np.where(seq % 1500 < 257, 0.0, 1.0),
        "tiny": np.where(seq % 1500 < 8, 0.0, 1.0),
        "ladder": np.where(
            seq % 1500 < np.array([1500, 500, 100, 20])[seq // 1500],
            0.0, 1.0),
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
        "total": pa.array(
            [None if i % 5 == 2 else Decimal(int(c) * 10**10 + i) / 100
             for i, c in enumerate(cents)], type=pa.decimal128(17, 2)),
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
    # sums of 27 digits: 128-bit states, two words a value on the device
    "decimal128-sum": ("key", (
        AggExpr(fn="sum", children=(col("total"),),
                return_type=DEC_WIDE_SUM),
        AggExpr(fn="avg", children=(col("total"),),
                return_type=DEC_WIDE_AVG))),
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


def _run(shape, mode, live, n_dev, scope=HINT):
    """One run against the serial engine: (the input each aggregate that
    chose worked on, in trace order — the deepest first; retries)."""
    fact = _fact(live)
    stage, ctx, serial = _plans(fact, shape, mode)
    S._SHRINK_HINT.clear()
    stats = {}
    before = retry.stats_snapshot()["retries"]
    with conf.scoped(scope):
        got = S.execute_plan_spmd(stage, ctx, data_mesh(n_dev),
                                  {"fact": fact}, stats=stats)
    want = _serial_reference(serial, {"fact": fact})
    assert got.num_rows == len(want) > 0
    assert _canon(got.to_pylist()) == _canon(want)
    marks = [a["input"] for a in stats["agg_inputs"].values()]
    return marks, retry.stats_snapshot()["retries"] - before, stats


# live rows -> (scope, {devices: (input, rows) of the first aggregate to
# choose}): the side it took and the width its body ran at.  On one device
# the 6,000 rows lie in 8,192 slots; on four, 1,500 in 2,048 each.
_FIRST = {
    # without rungs: live rows under the target, and over it (40 groups:
    # the cut loses nothing)
    "few": (HINT, {1: ("compact", 1024), 4: ("compact", 1024)}),
    "most": (HINT, {1: ("full", 8192), 4: ("full", 2048)}),
    # with rungs at 32 and 256 rows under the target's 1,024
    "at-rung": (RUNGS, {1: ("compact", 1024), 4: ("compact", 256)}),
    "over-rung": (RUNGS, {1: ("full", 8192), 4: ("compact", 1024)}),
    "tiny": (RUNGS, {1: ("compact", 32), 4: ("compact", 32)}),
    "ladder": (RUNGS, {1: ("full", 8192),
                       4: ("compact 3/4", "32/256/1024/2048")}),
}


# On one device the exchange is an identity: without rungs the first
# aggregate alone sees a table larger than the target.  On four the
# exchange hands the final aggregate 4 x (2 x 1024 / 4 + 8) = 2,080 slots,
# so it chooses too (and its live rows, the partial aggregates' groups,
# fit the target).  With rungs the final aggregate chooses on one device
# as well: its 1,024-slot input is wider than both.
@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("shape,mode", [
    ("sums", "partial"), ("sums", "single"), ("string-key", "partial"),
    ("decimal-sum", "partial"), ("decimal128-sum", "partial"),
    ("first", "partial"), ("first", "single"),
])
@pytest.mark.parametrize("live", sorted(_FIRST))
def test_both_sides_of_the_choice_give_the_serial_answer(
        shape, mode, live, n_dev):
    scope, first = _FIRST[live]
    marks, retries, stats = _run(shape, mode, live, n_dev, scope)
    assert retries == 0
    chose = 1 if mode == "single" or (n_dev == 1 and scope is HINT) else 2
    assert len(marks) == chose
    aggs = list(stats["agg_inputs"].values())
    if mode == "single" and n_dev == 4:
        # the exchange under a single-mode aggregate deals the 40 keys'
        # rows by hash, not by their place in the file
        if live == "most":
            # one device of the four is dealt under the target; the others
            # run at the exchange's 4 x (2 x 2048 / 4 + 8) slots
            assert (marks[0], aggs[0]["rows"]) == ("compact 1/4",
                                                   "1024/4128")
    else:
        assert (marks[0], aggs[0]["rows"]) == first[n_dev]
    # the final aggregate of four devices merges at most 4 x 40 groups
    assert marks[1:] == ["compact"][:chose - 1]
    for a in aggs[1:]:
        assert set(S.agg_widths(a)) <= \
            ({32, 256} if scope is RUNGS else {1024})


@pytest.mark.parametrize("scope", [HINT, RUNGS], ids=["hint", "rungs"])
@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("shape,mode", [
    ("sums", "partial"), ("sums", "single"), ("string-key", "partial"),
    ("first", "partial"),
])
def test_groups_past_the_target_trip_the_guard_and_climb_the_ladder(
        shape, mode, n_dev, scope):
    """6,000 groups (1,500 a device): the full side's cut would lose rows,
    its guard trips, the driver retries four times wider — as before there
    was a choice, with rungs or without; the answer comes from a rung of
    the ladder that holds them."""
    marks, retries, stats = _run(shape, mode, "groups", n_dev, scope)
    # one device: 4,096 trips again, 16,384 is no cut; four: 4,096 holds
    assert retries == (2 if n_dev == 1 else 1)
    [(_key, rung)] = S._SHRINK_HINT.items()
    assert rung == TARGET * (16 if n_dev == 1 else 4)
    # The rung that answered is past every table of one device, but it has
    # rungs of its own (4,096 and 1,024 under 16,384; 1,024 under 4,096)
    # and the tables are wider than those: each aggregate chooses, and
    # its 6,000 (1,500 a device) live rows take the full side.  On four
    # the exchange's 4 x (2 x 2048 / 4 + 8) = 4,128 slots are past the
    # rung too, and their 1,500 live rows fit it.
    chose = 1 if mode == "single" else 2
    assert len(marks) == chose
    if n_dev == 1:
        assert marks == ["full"] * chose
        assert {a["rows"] for a in stats["agg_inputs"].values()} == {8192}
    else:
        assert marks[-1] == "compact"
        assert marks[:-1] == ["full"][:chose - 1]


@pytest.mark.parametrize("shape", ["sums", "string-key", "first"])
@pytest.mark.parametrize("live,scope,rows,final_rows", [
    # device 0 keeps all of its 1,500 rows, the three others 75 each: one
    # runs the body at 2,048 rows, three at 1,024, the answer is one
    ("one-device", HINT, "1024/2048", 1024),
    # four devices, each at another width of one program: 20 rows at 32,
    # 100 at 256, 500 at 1,024 and 1,500 at the input's 2,048; the final
    # aggregate's few dozen groups a device take a rung
    ("ladder", RUNGS, "32/256/1024/2048", 256),
])
def test_devices_choose_for_their_own_rows(shape, live, scope, rows,
                                           final_rows):
    marks, retries, stats = _run(shape, "partial", live, 4, scope)
    assert retries == 0 and marks == ["compact 3/4", "compact"]
    first, final = stats["agg_inputs"].values()
    assert first["rows"] == rows and max(S.agg_widths(final)) == final_rows
    assert first["live"] == (1500 + 3 * 75 if live == "one-device"
                             else 2120)
    assert first["capacity"] == 4 * 2048
    assert S.agg_input_counts(stats["agg_inputs"]) == \
        {"agg_inputs": 2, "agg_inputs_compact": 1,
         "agg_inputs_below_cap": int(final_rows < TARGET)}


@pytest.mark.parametrize("scope", [HINT, RUNGS], ids=["hint", "rungs"])
@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("mode", ["partial", "single"])
def test_a_global_aggregate_over_no_rows_keeps_its_identity_row(mode,
                                                                n_dev,
                                                                scope):
    """count 0, sum null, from the narrowest side: no live row is no more
    than the target, or than its smallest rung."""
    marks, retries, stats = _run("global", mode, "none", n_dev, scope)
    assert retries == 0 and marks and set(marks) == {"compact"}
    assert {a["rows"] for a in stats["agg_inputs"].values()} == \
        {32 if scope is RUNGS else 1024}


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


# (test_one_program.py pins the text of the first two: one program,
# neither cuts anything)
_NO_CHOICE_PROGRAM = {
    # the default hint, 262,144 rows: the 8,192-row input is no larger,
    # and no wider than the smallest of its rungs, 8,192 and 65,536
    "input-within-target": {},
    # the shrink off: no cut and no rung, so nothing to compact to
    "shrink-off": {"auron.spmd.agg.capacity.hint": 0},
    # 1,048,576 rows, the ladder's first climb: one rung, 32,768 rows
    "input-under-the-one-rung": {
        "auron.spmd.agg.capacity.hint": 1 << 20},
}

# and those that leave one: the conditionals in the program, and the
# sides of each (the partial aggregate's, then the final one's)
_CHOICE_PROGRAM = {
    # the input is larger than the target, which has no rung: compact or
    # full; the final aggregate's 1,024 rows leave none
    "target-alone": (HINT, [2]),
    # 32, 256, 1,024 or full; the final aggregate: 32, 256 or its 1,024
    "two-rungs": (RUNGS, [4, 3]),
    # neither input is larger than 32,768, both are wider than its rung
    # of 1,024 (the other, 8,192, is not narrower than they are)
    "a-rung-under-an-input-within-target": (
        {"auron.spmd.agg.capacity.hint": 1 << 15}, [2, 2]),
}


def case_branches(text):
    """The number of branches of every `stablehlo.case` of a lowered
    program, in the text's order: a case's regions are printed between
    `({` and `})`, one `}, {` between two of them at the case's own
    indentation."""
    import re
    found = []
    lines = text.splitlines()
    for i, line in enumerate(lines):
        if '"stablehlo.case"' not in line:
            continue
        indent = re.match(r" *", line).group()
        sides = 1
        for later in lines[i + 1:]:
            if later.startswith(indent + "}, {"):
                sides += 1
            elif later.startswith(indent + "})"):
                break
        found.append(sides)
    return found


@pytest.mark.parametrize("case", sorted(_NO_CHOICE_PROGRAM))
def test_an_aggregate_with_no_larger_input_traces_no_choice(case):
    text = _lowered(_NO_CHOICE_PROGRAM[case])
    assert "stablehlo.case" not in text and "stablehlo.if" not in text


@pytest.mark.parametrize("case", sorted(_CHOICE_PROGRAM))
def test_an_aggregate_with_a_width_under_its_input_traces_one_choice(case):
    scope, sides = _CHOICE_PROGRAM[case]
    assert case_branches(_lowered(scope)) == sides


@pytest.mark.parametrize("scope,sides", [(HINT, 2), (RUNGS, 4)],
                         ids=["two-way", "four-way"])
def test_the_choice_is_one_conditional_with_no_collective_inside(scope,
                                                                 sides):
    """A compact side gathers by a scattered permutation and sorts
    nothing the full side does not; the guard's and the counter's `psum`
    lie outside every side, however many there are."""
    import jax
    from stage_spy import spied_program
    from test_stage_tracing import _eqns
    fact = _fact("few")
    stage, ctx, _serial_plan = _plans(fact, "sums", "partial")
    with conf.scoped(scope):
        program, inputs = spied_program(stage, ctx, data_mesh(4),
                                        {"fact": fact})
        found = list(_eqns(jax.make_jaxpr(program)(inputs).jaxpr))
    conds = [eqn for eqn, _inside in found if eqn.primitive.name == "cond"]
    # the partial and the final aggregate: both inputs are larger than the
    # target (the final one's the exchange's 2,080 slots)
    assert [len(eqn.params["branches"]) for eqn in conds] == [sides, sides]
    inside = [eqn.primitive.name for eqn, where in found
              if any(name == "cond" for name, _i in where)]
    assert "cumsum" in inside and "scatter" in inside
    assert not {"psum", "psum2", "all_to_all", "all_gather", "pmax"} \
        & set(inside)


# -- the counter ----------------------------------------------------------------

def test_counter_in_the_record_the_span_and_explain_analyze(tmp_path):
    """`agg_inputs` / `agg_inputs_compact` / `agg_inputs_below_cap` in the
    query record's totals and on `spmd.wait`'s args; `input=compact
    rows=<width> live=<rows> of <capacity>` on the aggregate's line of
    EXPLAIN ANALYZE.  Beside them `segment_bounds`, one an aggregate body
    traced (every side of a choice is), and `segment_reductions`, the
    reductions that took them."""
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
        assert (f" input=compact rows={TARGET} live={a['live']} of "
                f"{a['capacity']} cap={TARGET}" in lines[label])
    assert any("input=" not in ln for ln in lines.values())   # the final
    totals = tracing.find_query(res.query_id).metric_totals
    [wait] = [s for s in res.trace.snapshot() if s.name == "spmd.wait"]
    for where in (totals, wait.args, res.stage_totals()):
        assert where["agg_inputs"] == where["agg_inputs_compact"] \
            == len(aggs)
        assert where["agg_inputs_below_cap"] == 0     # the target, no rung
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
    assert plain_totals["agg_inputs_below_cap"] == 0
    assert plain_totals["segment_bounds"] == len(lines)
    # the choice's untaken side held as many reductions as the taken one
    assert (totals["segment_reductions"] * len(lines)
            == plain_totals["segment_reductions"]
            * (len(lines) + len(aggs)))
    assert plain.table.equals(res.table)
    # with rungs under the target every aggregate has a width under its
    # input, the final ones too, and says which its body ran at
    with conf.scoped({"auron.trace.enable": True, **RUNGS}):
        laddered = session.execute(plan)
    assert laddered.table.equals(res.table)
    rungs = laddered.stage_stats["agg_inputs"]
    assert len(rungs) == len(lines)
    below = [a for a in rungs.values() if max(S.agg_widths(a)) < TARGET]
    assert below and all(a["live"] <= a["rows"] for a in below)
    analyzed = laddered.explain_analyze()
    for a in rungs.values():
        assert f" input={a['input']} rows={a['rows']} live=" in analyzed
    [wait] = [s for s in laddered.trace.snapshot() if s.name == "spmd.wait"]
    for where in (tracing.find_query(laddered.query_id).metric_totals,
                  wait.args, laddered.stage_totals()):
        assert where["agg_inputs"] == len(rungs)
        assert where["agg_inputs_below_cap"] == len(below)

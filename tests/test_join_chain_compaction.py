"""A chain of inner broadcast joins over a source, its later joins run at
the width of the rows its first join left (`_StageTracer._join_chain`):
the choice the stage program makes from the live count — on every side,
on one device and on four, with direct and searched probes inside a side
— against the serial engine, row for row; the chains that do not engage;
what such a program lowers to; and where its counter goes."""

import numpy as np
import pyarrow as pa
import pytest

from auron_tpu.config import conf
from auron_tpu.frontend.converters import BroadcastJob, ShuffleJob
from auron_tpu.ir import expr as E
from auron_tpu.ir import plan as P
from auron_tpu.ir.expr import AggExpr, col, lit
from auron_tpu.ir.plan import JoinOn
from auron_tpu.ir.schema import DataType, from_arrow_schema
from auron_tpu.parallel import stage as S
from auron_tpu.parallel.mesh import data_mesh
from auron_tpu.runtime import retry, tracing
from test_agg_input_compaction import case_branches
from test_spmd_stage import _Ctx, _canon, _serial_reference

F64 = DataType.float64()
ROWS = 6000             # 8,192 slots on one device, 2,048 on each of four
QUARTER = ROWS // 4
# buckets from 32 rows on: the chain's rungs are a sixty-fourth and an
# eighth of the source's capacity — 128 and 1,024 rows on one device, 32
# and 256 on each of four
SMALL = {"auron.batch.capacity.min": 32}
MISS = 10_000           # a first-join key no dimension row holds


def _fact(kept) -> pa.Table:
    """6,000 fact rows; of each quarter of 1,500 (a device's rows, of
    four) the first `kept[q]` find their row of `da`, the first join's
    build side, and the others do not.  Every row finds its row of every
    later join's build side."""
    rng = np.random.default_rng(17)
    seq = np.arange(ROWS, dtype=np.int64)
    kept = np.asarray(kept)[seq // QUARTER]
    b = rng.integers(0, 30, ROWS).astype(np.int64)
    return pa.table({
        "seq": seq,
        "a": np.where(seq % QUARTER < kept, seq % 50, MISS).astype(np.int64),
        "b": b,
        "s": pa.array([f"s{i:03d}" for i in rng.integers(0, 20, ROWS)]),
        "c1": rng.integers(0, 6, ROWS).astype(np.int64),
        "c2": rng.integers(0, 4, ROWS).astype(np.int32),
        "v": rng.normal(0, 1, ROWS),
    })


def _dims(case: str):
    """The build sides: `da` (the first join: int64, direct), `db`
    (int64: direct, or sparse keys that the program searches), `dc` (a
    string key: searched) and `dd` (a composite key: searched)."""
    bk = np.arange(30, dtype=np.int64)
    if case == "sparse":
        bk = bk * 1000
    if case == "duplicate-direct":
        bk = np.append(bk, 7)
    ck = [f"s{i:03d}" for i in range(20)]
    if case == "duplicate-search":
        ck.append("s011")
    d1, d2 = np.meshgrid(np.arange(6, dtype=np.int64),
                         np.arange(4, dtype=np.int32), indexing="ij")
    return {
        "da": pa.table({"ak": np.arange(50, dtype=np.int64),
                        "aw": np.arange(50, dtype=np.float64)}),
        "db": pa.table({"bk": bk, "bw": np.arange(len(bk)) * 2.0}),
        "dc": pa.table({"ck": pa.array(ck),
                        "cw": np.arange(len(ck)) * 3.0}),
        "dd": pa.table({"dk1": d1.ravel(), "dk2": d2.ravel(),
                        "dw": np.arange(24) * 5.0}),
    }


def _join(left, rid, lk, rk, ctx, join_type="inner"):
    """(stage join, serial join) of `left` pairs with the dimension `rid`
    broadcast."""
    stage_left, serial_left = left
    right = ctx.srcs[rid]
    ctx.broadcasts["bc-" + rid] = BroadcastJob(
        rid="bc-" + rid, child=right, schema=None)
    on = JoinOn(left_keys=tuple(col(k) for k in lk),
                right_keys=tuple(col(k) for k in rk))
    return tuple(
        P.BroadcastJoin(left=lhs, right=rhs, on=on, join_type=join_type,
                        broadcast_side="right")
        for lhs, rhs in ((stage_left,
                          P.IpcReader(schema=None,
                                      resource_id="bc-" + rid)),
                         (serial_left, right)))


def _both(make, pair):
    return tuple(make(x) for x in pair)


def _plans(tables, shape="chain"):
    """(stage plan, ctx, serial plan): a filter over the fact source, the
    first join (`da`), a projection, an int64 join (`db`), a filter, a
    string-key join (`dc`) and a composite-key join (`dd`) — a chain of
    four; or one of the shapes that engage no chain:

    - `left` / `semi`: the `db` join a left / semi join;
    - `over-agg`: the chain over a final aggregate's output (partial →
      hash exchange → final by `seq`, `a`, `b`, `s`, `c1`, `c2`), not a source;
    - `one-join`: the first join alone."""
    ctx = _Ctx()
    ctx.srcs = {rid: P.FFIReader(schema=from_arrow_schema(t.schema),
                                 resource_id=rid)
                for rid, t in tables.items()}
    fact = ctx.srcs["fact"]
    if shape == "over-agg":
        keys = ("seq", "a", "b", "s", "c1", "c2")
        agg = dict(grouping=tuple(col(k) for k in keys), grouping_names=keys,
                   aggs=(AggExpr(fn="sum", children=(col("v"),),
                                 return_type=F64),),
                   agg_names=("v",))
        partial = P.Agg(child=fact, exec_mode="partial", **agg)
        ctx.exchanges["ex"] = ShuffleJob(
            rid="ex", child=partial, schema=None,
            partitioning=P.Partitioning(mode="hash", num_partitions=8,
                                        expressions=(col("seq"),)))
        below = (P.Agg(child=P.IpcReader(schema=None, resource_id="ex"),
                       exec_mode="final", **agg),
                 P.Agg(child=partial, exec_mode="final", **agg))
    else:
        below = _both(lambda x: P.Filter(child=x, predicates=(
            E.BinaryExpr(left=col("v"), op=">", right=lit(-100.0)),)),
            (fact, fact))
    first = _join(below, "da", ("a",), ("ak",), ctx)
    if shape == "one-join":
        return (*first[:1], ctx, first[1])
    names = ("seq", "b", "s", "c1", "c2", "v", "aw")
    projected = _both(lambda x: P.Projection(
        child=x, names=names + ("vw",),
        exprs=tuple(col(n) for n in names) + (E.BinaryExpr(
            left=col("v"), op="*", right=col("aw")),)), first)
    second = _join(projected, "db", ("b",), ("bk",), ctx,
                   {"left": "left", "semi": "left_semi"}.get(shape,
                                                              "inner"))
    filtered = _both(lambda x: P.Filter(child=x, predicates=(
        E.BinaryExpr(left=col("c1"), op="<", right=lit(5)),)), second)
    third = _join(filtered, "dc", ("s",), ("ck",), ctx)
    stage, serial = _join(third, "dd", ("c1", "c2"), ("dk1", "dk2"), ctx)
    return stage, ctx, serial


def _run(kept, n_dev, case="dense", shape="chain", scope=SMALL,
         retries=0):
    """One run against the serial engine: the run's stats."""
    tables = {"fact": _fact(kept), **_dims(case)}
    stage, ctx, serial = _plans(tables, shape)
    S._MATCH_FACTOR_HINT.clear()
    stats = {}
    before = retry.stats_snapshot()["retries"]
    with conf.scoped(scope):
        got = S.execute_plan_spmd(stage, ctx, data_mesh(n_dev), tables,
                                  stats=stats)
    want = _serial_reference(serial, tables)
    assert retry.stats_snapshot()["retries"] - before == retries
    assert got.num_rows == len(want)
    assert _canon(got.to_pylist()) == _canon(want)
    return stats


# rows each device's first join keeps -> (the side the chain took, the
# width its later joins ran at)
_SIDES_TAKEN = {
    # one device, 8,192 slots: rungs of 128 and 1,024 rows
    (1, (0, 0, 0, 0)): ("compact", 128),
    (1, (128, 0, 0, 0)): ("compact", 128),          # exactly a rung
    (1, (129, 0, 0, 0)): ("compact", 1024),         # one row over it
    (1, (256, 256, 256, 256)): ("compact", 1024),   # the upper rung
    (1, (1025, 0, 0, 0)): ("full", 8192),           # one row over both
    (1, (1500, 1500, 1500, 1500)): ("full", 8192),
    # four devices, 2,048 slots each: rungs of 32 and 256 rows
    (4, (0, 0, 0, 0)): ("compact", 32),
    (4, (32, 32, 32, 32)): ("compact", 32),
    (4, (33, 33, 33, 33)): ("compact", 256),
    (4, (257, 257, 257, 257)): ("full", 2048),
    # every side taken in one program: two devices at 32 rows, one at
    # 256, one at the full 2,048
    (4, (1500, 200, 20, 32)): ("compact 3/4", "32/256/2048"),
}


@pytest.mark.parametrize("case", ["dense", "sparse"])
@pytest.mark.parametrize("n_dev,kept", sorted(_SIDES_TAKEN))
def test_every_side_of_the_choice_gives_the_serial_answer(n_dev, kept, case):
    """The later joins — an int64 key probed by direct address (or
    searched, where its keys are sparse), a string key and a composite
    key, both searched — give one answer at every width."""
    stats = _run(kept, n_dev, case)
    [(label, chain)] = stats["join_chains"].items()
    assert (chain["chain"], chain["rows"]) == _SIDES_TAKEN[(n_dev, kept)]
    assert chain["live"] == sum(kept)
    assert chain["capacity"] == 8192
    probes = stats["join_probes"]
    assert probes[label] == "direct"
    assert sorted(probes.values()) == sorted(
        ["direct", "direct" if case == "dense" else "search", "search",
         "search"])


@pytest.mark.parametrize("scope,rows", [
    # one rung at the default buckets: an eighth of 8,192 slots
    ({}, 1024),
    # and none where the source's eighth is under the least bucket
    ({"auron.batch.capacity.min": 2048}, None),
])
def test_the_rungs_are_capacity_buckets(scope, rows):
    stats = _run((20, 20, 20, 20), 1, scope=scope)
    chains = stats["join_chains"]
    if rows is None:
        assert chains == {}
    else:
        assert [c["rows"] for c in chains.values()] == [rows]


@pytest.mark.parametrize("case", ["duplicate-direct", "duplicate-search"])
@pytest.mark.parametrize("n_dev,kept", [
    (1, (100, 0, 0, 0)), (1, (500, 0, 0, 0)), (1, (1500,) * 4),
    (4, (1500, 200, 20, 32)),
])
def test_a_duplicate_build_key_trips_the_retry_on_every_side(n_dev, kept,
                                                             case):
    """The duplicate-key trip depends on the build side alone and is
    computed before the choice: whichever side a device takes, the run
    retries once with pair expansion — which traces no chain — and gives
    the serial answer."""
    stats = _run(kept, n_dev, case, retries=1)
    assert stats["join_chains"] == {}
    assert list(S._MATCH_FACTOR_HINT.values()) == [4]


@pytest.mark.parametrize("n_dev", [1, 4])
@pytest.mark.parametrize("shape", ["left", "semi", "over-agg", "one-join"])
def test_a_shape_that_is_no_chain_engages_nothing(shape, n_dev):
    """A left or a semi join breaks the chain (the first join is left
    alone), a chain over an aggregate's output has no source's width to
    cut, and one join is no chain."""
    stats = _run((100, 10, 0, 1500), n_dev, shape=shape)
    assert stats["join_chains"] == {}
    assert S.chain_counts(stats["join_chains"]) == \
        {"join_chains": 0, "join_chains_compact": 0}


def _lowered(shape, n_dev=1, scope=SMALL):
    from stage_spy import spied_program
    tables = {"fact": _fact((100, 0, 0, 0)), **_dims("dense")}
    stage, ctx, _serial = _plans(tables, shape)
    with conf.scoped(scope):
        program, inputs = spied_program(stage, ctx, data_mesh(n_dev),
                                        tables)
        return program, inputs


@pytest.mark.parametrize("n_dev", [1, 4])
def test_the_choice_is_one_switch_with_no_collective_inside(n_dev):
    """The build sides' broadcasts and the build halves lie before the
    choice, the guards' and counters' `psum`s after it; inside each side
    the compaction's running count and scatter, and each later join's
    probe half — a `lax.cond` between the direct gather and the search
    where its key is an integer, the search alone where it is a string
    or two columns."""
    import jax
    from test_stage_tracing import _eqns
    program, inputs = _lowered("chain", n_dev)
    found = list(_eqns(jax.make_jaxpr(program)(inputs).jaxpr))
    conds = [(eqn, [i for name, i in where if name == "cond"])
             for eqn, where in found if eqn.primitive.name == "cond"]
    # outside any conditional: the first join's choice of probe, `db`'s
    # build half and the chain's switch
    assert [len(eqn.params["branches"]) for eqn, sides in conds
            if not sides] == [2, 2, 3]
    # inside the switch: `db`'s probe half, once a side
    assert [sides for eqn, sides in conds if sides] == [[0], [1], [2]]
    inside = [eqn.primitive.name for eqn, where in found
              if any(name == "cond" for name, _i in where)]
    assert "cumsum" in inside and "scatter" in inside
    assert not {"psum", "psum2", "all_to_all", "all_gather", "pmax"} \
        & set(inside)


@pytest.mark.parametrize("shape,sides", [
    # the first join's probe choice, `db`'s build half; the chain's three
    # sides, each holding `db`'s probe half; `dc` and `dd` choose nothing
    ("chain", [2, 2, 3, 2, 2, 2]),
    ("left", [2, 2]),
    ("over-agg", [2, 2]),
    ("one-join", [2]),
])
def test_the_conditionals_of_the_program(shape, sides):
    program, inputs = _lowered(shape)
    assert case_branches(program.lower(inputs).as_text()) == sides


def test_counter_in_the_record_the_span_and_explain_analyze(tmp_path):
    """The benchmark's query 7 at its configuration's `rehearse_rows`, as
    the session runs it on one device: a chain of four joins over the
    scan of store_sales (65,536 slots), whose first join keeps a few
    hundred rows — the rung of 1,024.  `join_chains` /
    `join_chains_compact` in the query record's totals, on `spmd.wait`'s
    args and in `stage_totals()`; `chain=compact rows=<width>
    live=<rows> of <slots>` on the first join's line of EXPLAIN ANALYZE,
    beside its probe."""
    from auron_tpu.frontend.session import AuronSession
    from auron_tpu.it.oracle import PyArrowEngine
    from benchmarks.harness import cells, compare, datagen
    from benchmarks.queries import q07
    cell = cells.load_cell("tpcds-sf1.q07")
    cat = datagen.generate(str(tmp_path), q07.SCANS,
                           cell.config["rehearse_rows"],
                           cell.config["data_seed"], 5)
    params = cell.traffic["param_sets"][0]
    plan = q07.build_plan(cat, params)
    session = AuronSession(foreign_engine=PyArrowEngine())
    with conf.scoped({"auron.trace.enable": True}):
        res = session.execute(plan)
    assert res.spmd
    assert res.stage_stats["join_chains"] == {"broadcast_join#11": {
        "chain": "compact", "rows": 1024,
        "live": res.stage_stats["join_chains"]["broadcast_join#11"]["live"],
        "capacity": 65536}}
    live = res.stage_stats["join_chains"]["broadcast_join#11"]["live"]
    assert 0 < live <= 1024
    text = res.explain_analyze()
    [line] = [ln for ln in text.splitlines()
              if ln.strip().startswith("broadcast_join#11 ")]
    assert line.endswith(" probe=direct chain=compact rows=1024 "
                         f"live={live} of 65536")
    assert text.count(" chain=") == 1 and text.count("probe=direct") == 4
    totals = tracing.find_query(res.query_id).metric_totals
    [wait] = [s for s in res.trace.snapshot() if s.name == "spmd.wait"]
    for where in (totals, wait.args, res.stage_totals()):
        assert (where["join_chains"], where["join_chains_compact"]) == (1, 1)
        assert where["join_probes_direct"] == 4
    want = q07.reference(cat.read, params)
    assert compare.judge(compare.compare_tables(res.table, want),
                         q07.LIMITS)["ok"]
    # with buckets from 16,384 rows no rung is narrower than a fourth of
    # the scan: no chain is traced, and the counter counts nothing
    with conf.scoped({"auron.trace.enable": True,
                      "auron.batch.capacity.min": 16384}):
        plain = session.execute(plan)
    assert plain.stage_stats["join_chains"] == {}
    assert " chain=" not in plain.explain_analyze()
    plain_totals = tracing.find_query(plain.query_id).metric_totals
    assert (plain_totals["join_chains"],
            plain_totals["join_chains_compact"]) == (0, 0)
    assert plain.table.equals(res.table)


@pytest.mark.parametrize("width", [8, 16, 6])
def test_a_strings_bytes_cross_the_choice_as_word_columns(width):
    """A side hands a string's [rows, width] bytes out as 1-D columns of
    32-bit words, four bytes a word (of single bytes where the width is no
    multiple of four), and they come back byte for byte."""
    import jax.numpy as jnp
    rng = np.random.default_rng(width)
    data = jnp.asarray(rng.integers(0, 256, (37, width)).astype(np.uint8))
    words = S._byte_words(data)
    assert len(words) == (width // 4 if width % 4 == 0 else width)
    assert all(w.shape == (37,) for w in words)
    assert (np.asarray(S._word_bytes(words)) == np.asarray(data)).all()

"""Decimals of 19-38 digits on the device (PR 35): every operation the
stage program has over `DeviceDecimal128Column`, through
`execute_plan_spmd` (which raises where the serial engine would have to
take the plan: a run that returns is a run with no fallback), on one device
and on four virtual ones, against Python integers — values inside int64,
beyond +-2**63, ties at both rounding places, overflow to null, nulls."""

import decimal
from decimal import Decimal

import numpy as np
import pyarrow as pa
import pytest

from auron_tpu.frontend.converters import BroadcastJob, ShuffleJob
from auron_tpu.ir import expr as E
from auron_tpu.ir import plan as P
from auron_tpu.ir.expr import AggExpr, col
from auron_tpu.ir.schema import DataType, from_arrow_schema
from auron_tpu.parallel import stage as S
from auron_tpu.parallel.mesh import data_mesh
from test_spmd_stage import _Ctx

N_DEVS = (1, 4)
CTX = decimal.Context(prec=80)
I64 = DataType.int64()
W2 = DataType.decimal(38, 2)
M6 = DataType.decimal(21, 6)

# unscaled values: small, at the rounding ties of a cut of two and of four
# digits, around +-2**63, far beyond it, at the type's ends
EDGES = [0, 1, -1, 49, 50, -50, 149, 150, -150, 4999, 5000, -5000, 15000,
         2**63 - 1, 2**63, -2**63, -2**63 - 1, 2**64, 10**20, -10**20,
         10**20 + 50, 10**36 + 7, 10**37, -10**37, 10**38 - 1, -(10**38 - 1)]


def _unscaled(v, scale):
    return None if v is None else int(v.scaleb(scale, CTX))


def _dec(u, scale):
    return None if u is None else Decimal(u).scaleb(-scale, CTX)


def _half_up(n, d):
    q = (2 * abs(n) + d) // (2 * d)
    return -q if n < 0 else q


def _fits(u, precision):
    return u if u is not None and abs(u) < 10 ** precision else None


def _values():
    rng = np.random.default_rng(5)
    rand = [int(rng.integers(-2**62, 2**62)) * int(rng.integers(1, 2**40))
            for _ in range(40)]
    wide = EDGES + rand + [None, None]
    narrow = [_fits(v, 17) if v is not None and abs(v) < 10**17
              else int(rng.integers(-10**17 + 1, 10**17)) for v in wide]
    narrow[3] = None
    return wide, narrow


WIDE, NARROW = _values()


def _fact():
    n = len(WIDE)
    return pa.table({
        "seq": pa.array(range(n), pa.int64()),
        "w": pa.array([_dec(v, 2) for v in WIDE], pa.decimal128(38, 2)),
        "v": pa.array([_dec(v, 2) for v in reversed(WIDE)],
                      pa.decimal128(38, 2)),
        "n": pa.array([_dec(v, 2) for v in NARROW], pa.decimal128(17, 2)),
        "m": pa.array([_dec(None if v is None else v % 10**21, 6)
                       for v in WIDE], pa.decimal128(21, 6)),
    })


def _source(table, rid="fact"):
    return P.FFIReader(schema=from_arrow_schema(table.schema),
                       resource_id=rid)


def _execute(plan, ctx, tables, n_dev, by="seq"):
    stats = {}
    got = S.execute_plan_spmd(plan, ctx, data_mesh(n_dev), tables,
                              stats=stats)
    return got.sort_by(by), stats


def _column(table, name, scale):
    assert pa.types.is_decimal(table.schema.field(name).type)
    return [_unscaled(v, scale) for v in table[name].to_pylist()]


def _project(exprs):
    """seq and the expressions as c0, c1, ... over the fact table, under a
    filter that keeps every row: a projection at the plan's root would be
    peeled into the driver's tail and run by the serial engine."""
    fact = _fact()
    names = tuple(f"c{i}" for i in range(len(exprs)))
    projected = P.Projection(child=_source(fact),
                             exprs=(col("seq"),) + tuple(exprs),
                             names=("seq",) + names)
    return fact, P.Filter(child=projected,
                          predicates=(E.IsNotNull(child=col("seq")),))


# -- Cast -------------------------------------------------------------------

CASTS = {
    # name -> (source column, its values, its scale, target type)
    "wide-to-more-scale": ("w", WIDE, 2, DataType.decimal(38, 4)),
    "wide-to-less-scale": ("w", WIDE, 2, DataType.decimal(38, 0)),
    "wide-to-fewer-digits": ("w", WIDE, 2, DataType.decimal(22, 2)),
    "wide-down-to-one-word": ("w", WIDE, 2, DataType.decimal(18, 1)),
    "narrow-up-to-wide": ("n", NARROW, 2, DataType.decimal(24, 7)),
    "narrow-up-past-its-digits": ("n", NARROW, 2, DataType.decimal(19, 5)),
}


@pytest.mark.parametrize("n_dev", N_DEVS)
@pytest.mark.parametrize("case", sorted(CASTS))
def test_cast_rescales_by_a_power_of_ten_half_up_null_past_precision(
        case, n_dev):
    name, values, scale, dst = CASTS[case]
    fact, plan = _project([E.Cast(child=col(name), dtype=dst)])
    got, stats = _execute(plan, _Ctx(), {"fact": fact}, n_dev)
    want = []
    for v in values:
        if v is None:
            want.append(None)
            continue
        shift = dst.scale - scale
        u = v * 10 ** shift if shift >= 0 else _half_up(v, 10 ** -shift)
        want.append(_fits(u, dst.precision))
    assert _column(got, "c0", dst.scale) == want
    assert any(w is None and v is not None for w, v in zip(want, values)) \
        or case in ("wide-to-less-scale", "narrow-up-to-wide")
    assert stats["wide_columns"]


# -- Multiply by a decimal literal ----------------------------------------------

def _times(left, factor, factor_type, dst):
    return E.ScalarFunctionCall(
        name="check_overflow",
        args=(E.BinaryExpr(left=left, op="*",
                           right=E.Literal(value=factor,
                                           dtype=factor_type)),),
        return_type=dst)


PRODUCTS = {
    # name -> (column, values, scale, literal, its type, result type)
    "query-1s": ("m", [None if v is None else v % 10**21 for v in WIDE], 6,
                 Decimal("1.200000"), M6, DataType.decimal(24, 7)),
    "negative-factor-rounded": (
        "m", [None if v is None else v % 10**21 for v in WIDE], 6,
        Decimal("-0.333333"), M6, DataType.decimal(28, 8)),
    "wide-by-narrow-literal": ("w", WIDE, 2, Decimal("7.5"),
                               DataType.decimal(2, 1),
                               DataType.decimal(38, 3)),
    "narrow-to-a-wide-product": ("n", NARROW, 2, Decimal("123456.789"),
                                 DataType.decimal(9, 3),
                                 DataType.decimal(27, 5)),
    "scale-adjusted-by-spark": ("w", WIDE, 2, Decimal("1.23456789"),
                                DataType.decimal(9, 8),
                                DataType.decimal(38, 6)),
}


@pytest.mark.parametrize("n_dev", N_DEVS)
@pytest.mark.parametrize("case", sorted(PRODUCTS))
def test_multiply_is_exact_at_s1_plus_s2_then_rounded_to_the_result_type(
        case, n_dev):
    name, values, scale, factor, ftype, dst = PRODUCTS[case]
    fact, plan = _project([_times(col(name), factor, ftype, dst)])
    got, _stats = _execute(plan, _Ctx(), {"fact": fact}, n_dev)
    f = _unscaled(factor, ftype.scale)
    want = []
    for v in values:
        if v is None:
            want.append(None)
            continue
        shift = dst.scale - scale - ftype.scale
        p = v * f
        u = p * 10 ** shift if shift >= 0 else _half_up(p, 10 ** -shift)
        want.append(_fits(u, dst.precision))
    assert _column(got, "c0", dst.scale) == want


# -- comparisons and null tests ---------------------------------------------

OPS = {"==": lambda a, b: a == b, "!=": lambda a, b: a != b,
       "<": lambda a, b: a < b, "<=": lambda a, b: a <= b,
       ">": lambda a, b: a > b, ">=": lambda a, b: a >= b}


@pytest.mark.parametrize("n_dev", N_DEVS)
@pytest.mark.parametrize("op", sorted(OPS))
def test_comparisons_are_the_signed_128_bit_order(op, n_dev):
    """Wide against wide, wide against a narrow column cast to its type,
    and as a filter's predicate: null where either side is."""
    as_wide = E.Cast(child=col("n"), dtype=W2)
    fact, plan = _project([
        E.BinaryExpr(left=col("w"), op=op, right=col("v")),
        E.BinaryExpr(left=col("w"), op=op, right=as_wide)])
    got, _stats = _execute(plan, _Ctx(), {"fact": fact}, n_dev)

    def want(lefts, rights):
        return [None if a is None or b is None else OPS[op](a, b)
                for a, b in zip(lefts, rights)]
    assert got["c0"].to_pylist() == want(WIDE, list(reversed(WIDE)))
    assert got["c1"].to_pylist() == want(WIDE, NARROW)
    kept = P.Filter(child=_source(fact), predicates=(
        E.BinaryExpr(left=col("w"), op=op, right=col("v")),))
    rows, _stats = _execute(kept, _Ctx(), {"fact": fact}, n_dev)
    assert rows["seq"].to_pylist() == [
        i for i, t in enumerate(want(WIDE, list(reversed(WIDE)))) if t]
    # what the filter let through comes back as Arrow decimal128, exact
    assert _column(rows, "w", 2) == [WIDE[i] for i in rows["seq"].to_pylist()]


@pytest.mark.parametrize("n_dev", N_DEVS)
def test_null_tests_read_the_validity_word(n_dev):
    fact, plan = _project([E.IsNull(child=col("w")),
                           E.IsNotNull(child=col("w"))])
    got, _stats = _execute(plan, _Ctx(), {"fact": fact}, n_dev)
    assert got["c0"].to_pylist() == [v is None for v in WIDE]
    assert got["c1"].to_pylist() == [v is not None for v in WIDE]


# -- Sum and Average ----------------------------------------------------------

def _groups():
    """(key, wide value, narrow value) rows.  Group 0: 4,096 rows whose
    narrow sum is odd — Divide(sum, count) at scale 13 is a tie (10**11
    holds eleven twos, the count twelve).  Group 1: 32 rows with an odd
    sum — the quotient at scale 13 is exact and the cast to scale 6 a tie.
    Group 2: the same, negative.  Group 3: two values whose wide sum
    passes 38 digits (and 128 bits).  Group 4: nulls alone.  Group 5:
    values beyond 2**63 that cancel to a small sum.  Groups 6-29: random,
    with nulls."""
    rng = np.random.default_rng(9)
    rows = [(0, 10**20 + i, 1001 if i == 0 else 1000) for i in range(4096)]
    rows += [(1, -10**25, 3 if i == 0 else 2) for i in range(32)]
    rows += [(2, 7, -3 if i == 0 else -2) for i in range(32)]
    rows += [(3, 9 * 10**37, 10**16), (3, 9 * 10**37, -10**16 + 1)]
    rows += [(4, None, None)] * 5
    rows += [(5, 2**70, 5), (5, -2**70, None), (5, 10**30, 6),
             (5, -10**30 + 1, 7)]
    for _ in range(600):
        k = int(rng.integers(6, 30))
        w = None if rng.random() < 0.1 else \
            int(rng.integers(-2**62, 2**62)) * int(rng.integers(1, 2**50))
        n = None if rng.random() < 0.1 else \
            int(rng.integers(-10**17 + 1, 10**17))
        rows.append((k, w, n))
    order = rng.permutation(len(rows))
    return [rows[i] for i in order]


GROUPS = _groups()


def _grouped_table():
    return pa.table({
        "k": pa.array([r[0] for r in GROUPS], pa.int64()),
        "w": pa.array([_dec(r[1], 2) for r in GROUPS],
                      pa.decimal128(38, 2)),
        "n": pa.array([_dec(r[2], 2) for r in GROUPS],
                      pa.decimal128(17, 2)),
    })


def _two_phase(table, aggs, names):
    agg = dict(grouping=(col("k"),), grouping_names=("k",), aggs=aggs,
               agg_names=names)
    ctx = _Ctx()
    ctx.exchanges["ex"] = ShuffleJob(
        rid="ex", child=P.Agg(child=_source(table), exec_mode="partial",
                              **agg),
        partitioning=P.Partitioning(mode="hash", num_partitions=4,
                                    expressions=(col("k"),)), schema=None)
    return P.Agg(child=P.IpcReader(schema=None, resource_id="ex"),
                 exec_mode="final", **agg), ctx


@pytest.fixture(scope="module", params=N_DEVS)
def aggregated(request):
    """sum(w) decimal(38,2), sum(n) decimal(27,2) and avg(n) decimal(21,6)
    by k, partial -> hash exchange -> final, on `param` devices."""
    table = _grouped_table()
    plan, ctx = _two_phase(table, (
        AggExpr(fn="sum", children=(col("w"),), return_type=W2),
        AggExpr(fn="sum", children=(col("n"),),
                return_type=DataType.decimal(27, 2)),
        AggExpr(fn="avg", children=(col("n"),), return_type=M6),
        AggExpr(fn="count", children=(col("n"),), return_type=I64)),
        ("sw", "sn", "an", "cn"))
    got, stats = _execute(plan, ctx, {"fact": table}, request.param, by="k")
    assert got["k"].to_pylist() == sorted({r[0] for r in GROUPS})
    return got, stats


def _by_group(column):
    out = {}
    for r in GROUPS:
        if r[column] is not None:
            out.setdefault(r[0], []).append(r[column])
    return out


@pytest.mark.parametrize("k", range(30))
def test_sum_carries_between_the_words_and_overflows_to_null(aggregated, k):
    got, _stats = aggregated
    wide, narrow = _by_group(1), _by_group(2)
    want_w = _fits(sum(wide[k]), 38) if k in wide else None
    want_n = sum(narrow[k]) if k in narrow else None
    assert _column(got, "sw", 2)[k] == want_w
    assert _column(got, "sn", 2)[k] == want_n
    if k == 3:
        assert want_w is None          # the overflow, not an empty group
    if k == 5:
        assert want_w == 1 and abs(wide[k][0]) > 2**63


@pytest.mark.parametrize("k", range(30))
def test_average_is_two_roundings_in_sparks_order(aggregated, k):
    """Divide(sum decimal(27,2), count decimal(20,0)) half up at
    decimal(38,13), then the cast half up to decimal(21,6)."""
    got, _stats = aggregated
    narrow = _by_group(2)
    if k not in narrow:
        assert got["an"].to_pylist()[k] is None
        assert got["cn"].to_pylist()[k] == 0
        return
    total, count = sum(narrow[k]), len(narrow[k])
    q13 = _half_up(total * 10**11, count)
    assert _column(got, "an", 6)[k] == _half_up(q13, 10**7)
    assert got["cn"].to_pylist()[k] == count
    if k == 0:                         # a tie at the first rounding
        assert (2 * total * 10**11) % (2 * count) == count
    if k in (1, 2):                    # a tie at the second
        assert (total * 10**11) % count == 0 and abs(q13) % 10**7 == 5 * 10**6


def test_the_aggregates_states_and_results_are_counted_as_wide(aggregated):
    _got, stats = aggregated
    wide = stats["wide_columns"]
    # the source's w; the partial aggregate's buffers of sum(w), sum(n)
    # and avg(n), through the exchange; the final one's sw, sn, an
    assert sorted(wide.values()) == [1, 3, 3, 3]
    assert S.wide_totals(stats)["wide_decimal_columns"] == 10


# -- movement ---------------------------------------------------------------

@pytest.mark.parametrize("n_dev", N_DEVS)
def test_a_join_carries_a_wide_payload_from_the_broadcast_side(n_dev):
    """The build side's wide column through the broadcast (all_gather on
    four devices) and the probe's gather, the probe side's through the
    join; the fetch hands both back as Arrow decimal128."""
    fact = _fact()
    dim = pa.table({
        "dk": pa.array(range(0, len(WIDE), 2), pa.int64()),
        "dw": pa.array([_dec(WIDE[i], 2) for i in range(0, len(WIDE), 2)],
                       pa.decimal128(38, 2))})
    ctx = _Ctx()
    ctx.broadcasts["bc"] = BroadcastJob(rid="bc", child=_source(dim, "dim"),
                                        schema=None)
    plan = P.BroadcastJoin(
        left=_source(fact), right=P.IpcReader(schema=None, resource_id="bc"),
        on=P.JoinOn(left_keys=(col("seq"),), right_keys=(col("dk"),)),
        join_type="inner", broadcast_side="right")
    got, stats = _execute(plan, ctx, {"fact": fact, "dim": dim}, n_dev)
    keys = got["seq"].to_pylist()
    assert keys == list(range(0, len(WIDE), 2))
    assert _column(got, "dw", 2) == [WIDE[i] for i in keys]
    assert _column(got, "w", 2) == [WIDE[i] for i in keys]
    assert got.schema.field("dw").type == pa.decimal128(38, 2)
    if n_dev > 1:
        assert stats["broadcasts"]


@pytest.mark.parametrize("n_dev", N_DEVS)
def test_an_exchange_moves_both_words_of_every_row(n_dev):
    """A hash exchange on `seq` (all_to_all on four devices) under a
    single-mode aggregate that hands each row back as a group of one."""
    fact = _fact()
    ctx = _Ctx()
    ctx.exchanges["ex"] = ShuffleJob(
        rid="ex", child=_source(fact),
        partitioning=P.Partitioning(mode="hash", num_partitions=4,
                                    expressions=(col("seq"),)), schema=None)
    plan = P.Agg(child=P.IpcReader(schema=None, resource_id="ex"),
                 exec_mode="single", grouping=(col("seq"),),
                 grouping_names=("seq",),
                 aggs=(AggExpr(fn="sum", children=(col("w"),),
                               return_type=W2),), agg_names=("s",))
    got, stats = _execute(plan, ctx, {"fact": fact}, n_dev)
    assert _column(got, "s", 2) == WIDE
    if n_dev > 1:
        [moved] = stats["exchanges"].values()
        assert moved["rows"] == len(WIDE) and moved["rows_moved"] > 0


# -- what stays out ---------------------------------------------------------

KEY_USES = {
    "group key": lambda src: P.Agg(
        child=src, exec_mode="single", grouping=(col("w"),),
        grouping_names=("w",),
        aggs=(AggExpr(fn="count", children=(col("seq"),), return_type=I64),),
        agg_names=("c",)),
    "join key": lambda src: P.HashJoin(
        left=src, right=src,
        on=P.JoinOn(left_keys=(col("w"),), right_keys=(col("w"),)),
        join_type="inner", build_side="right"),
    "sort key": lambda src: P.Sort(
        child=src, sort_exprs=(E.SortExpr(child=col("w"), asc=True,
                                          nulls_first=True),),
        fetch_limit=3),
    "window argument": lambda src: P.Window(
        child=src, window_funcs=(P.WindowFuncCall(
            fn="row_number", args=(), agg=None, return_type=DataType.int32(),
            name="rn"),),
        partition_by=(col("seq"),),
        order_by=(E.SortExpr(child=col("w"), asc=True, nulls_first=True),)),
}


@pytest.mark.parametrize("use", sorted(KEY_USES))
def test_a_wide_decimal_as_a_key_is_refused_by_name(use):
    """By `iter_spmd_rejections`, before anything is read or traced: the
    program holds a wide decimal as a value, never where it would need
    its order or its hash."""
    fact = _fact()
    plan = KEY_USES[use](_source(fact))
    reasons = [r for _node, r in S.iter_spmd_rejections(plan, _Ctx())]
    assert f"a wide decimal (decimal(38,2)) as {use}" in reasons
    with pytest.raises(S.SpmdUnsupported):
        S.precheck_plan(plan, _Ctx())


def test_a_plan_that_names_no_wide_decimal_is_not_walked_for_one():
    narrow = pa.table({"k": pa.array([1, 2], pa.int64()),
                       "n": pa.array([Decimal("1.50"), None],
                                     pa.decimal128(17, 2))})
    plan = KEY_USES["sort key"](_source(narrow))
    assert not S._mentions_wide_decimal(plan)
    assert S._mentions_wide_decimal(KEY_USES["sort key"](_source(_fact())))

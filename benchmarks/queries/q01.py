"""TPC-DS query 1 (v3 spec, Appendix B query1.tpl): the customers who, in
one year, returned to a store of one state more than 1.2 times what that
store's customers returned on average; first 100 by customer id.

    with customer_total_return as
     (select sr_customer_sk as ctr_customer_sk, sr_store_sk as ctr_store_sk,
             sum([AGG_FIELD]) as ctr_total_return
      from store_returns, date_dim
      where sr_returned_date_sk = d_date_sk and d_year = [YEAR]
      group by sr_customer_sk, sr_store_sk)
    select c_customer_id
    from customer_total_return ctr1, store, customer
    where ctr1.ctr_total_return >
            (select avg(ctr_total_return) * 1.2
             from customer_total_return ctr2
             where ctr1.ctr_store_sk = ctr2.ctr_store_sk)
      and s_store_sk = ctr1.ctr_store_sk and s_state = '[STATE]'
      and ctr1.ctr_customer_sk = c_customer_sk
    order by c_customer_id limit 100

`params` are the template's substitution parameters (the spec's
qualification values are YEAR 2000, STATE TN, AGG_FIELD SR_RETURN_AMT) and
`SELECT`, the select list: "c_customer_id" is the template's;
"c_customer_id, ctr_store_sk, ctr_total_return, ctr_threshold" is the same
plan with those columns carried to the output and the order made unique
(c_customer_id, ctr_store_sk).  The second is a stated departure from the
template, there for one reason: the template hands back customer ids
alone, cut to 100, and no comparison of that with a reference can see a
threshold that is wrong in its seventh place, or in its first.

The plan is the physical plan Spark 3 hands over for this text, with its
types: sum(sr_return_amt) over decimal(7,2) is a sum of unscaled longs made
decimal(17,2) (DecimalAggregates); avg over decimal(17,2) keeps a
decimal(27,2) sum and a count and gives decimal(21,6) through
Divide(sum, count) at decimal(38,13), both rounded half up (17 + 4 > 15
digits: no rewrite to doubles); avg * 1.2 is CheckOverflow(avg * 1.200000,
decimal(24,7)); the comparison casts the total to decimal(24,7).  No
double anywhere.  The CTE is inlined twice: ctr1's scan drops null
customers (it joins customer), ctr2's does not (a null customer is a
group, and counts in its store's average), so the two relations differ
and the program computes both.  The subquery is decorrelated into an
aggregate by store, broadcast (102 rows) and joined on the store key with
the comparison as the join's residual condition; store (filtered to the
state) is broadcast; customer (500,000 rows at SF10, about 12 MB of the two
columns, over Spark's 10 MB broadcast threshold) is joined by sort-merge
under hash exchanges.
"""

from decimal import Decimal

import numpy as np
import pyarrow as pa

try:
    # the stage program's own word on what it holds (PR 35)
    from auron_tpu.columnar.batch import stage_holds  # noqa: F401
except ImportError:
    raise SystemExit(
        "benchmark: this program keeps decimals of 19-38 digits on the "
        "host (no columnar.batch.stage_holds): it cannot run query 1 as "
        "one stage program, which is what the configuration states; its "
        "serial engine would take minutes an execute at SF10 and answers "
        "with doubles in the plan") from None

from benchmarks.harness import refmath
from benchmarks.harness.plans import (I32, I64, STR, DataType, Field, Schema,
                                      agg, bhj, falias, fcall, fcol, ffilter,
                                      flit, fproject, so, take_ordered,
                                      two_phase_agg)
from benchmarks.harness.plans_wide import (bhj_where, smj,
                                           two_phase_decimal_avg)
from benchmarks.tables._common import decimal_array

MONEY = DataType.decimal(7, 2)
TOTAL = DataType.decimal(17, 2)          # sum of money
AVG_TOTAL = DataType.decimal(21, 6)      # avg of that
THRESHOLD = DataType.decimal(24, 7)      # avg * 1.2
FACTOR = Decimal("1.200000")             # 1.2 promoted to decimal(21,6)
# table -> the columns the plan's scans project: what is generated, what the
# reference reads, and what the roofline's bytes count
SCANS = {
    "store_returns": ["sr_returned_date_sk", "sr_customer_sk", "sr_store_sk",
                      "sr_return_amt"],
    "date_dim": ["d_date_sk", "d_year"],
    "store": ["s_store_sk", "s_state"],
    "customer": ["c_customer_sk", "c_customer_id"],
}
# how far a timed answer may lie from the reference: PERF.md section 2 has
# the readings each limit was set from (the query returns no double, so
# `float_rel_gap` reads 0 unless a column's type is wrong: then infinity)
LIMITS = {"rows_differ": 0, "float_rel_gap": 1e-10}
_OUT_TYPES = {"c_customer_id": STR, "ctr_store_sk": I64,
              "ctr_total_return": TOTAL, "ctr_threshold": THRESHOLD}


def select_list(params):
    return [c.strip() for c in params["SELECT"].split(",")]


def _all(*conds):
    out = conds[0]
    for c in conds[1:]:
        out = fcall("And", out, c)
    return out


def _not_null(name, dtype):
    return fcall("IsNotNull", fcol(name, dtype))


def _keep(node, names):
    fields = {f.name: f for f in node.output.fields}
    return fproject(node, [fcol(c, fields[c].dtype) for c in names],
                    Schema(tuple(fields[c] for c in names)))


def _customer_total_return(cat, params, customers_not_null):
    """The CTE, once: returns of the year by (customer, store), their
    amounts summed as unscaled longs and made decimal(17,2)."""
    keys = ["sr_returned_date_sk", "sr_store_sk"] + \
        (["sr_customer_sk"] if customers_not_null else [])
    sr = ffilter(cat.scan("store_returns", SCANS["store_returns"]),
                 _all(*(_not_null(k, I64) for k in keys)))
    year = fcall("EqualTo", fcol("d_year", I32), flit(int(params["YEAR"])))
    dd = _keep(ffilter(cat.scan("date_dim", SCANS["date_dim"],
                                pushed_filters=[year]),
                       _all(year, _not_null("d_date_sk", I64))),
               ["d_date_sk"])
    amount = params["AGG_FIELD"].lower()
    j = _keep(bhj(sr, dd, fcol("sr_returned_date_sk", I64),
                  fcol("d_date_sk", I64)),
              ["sr_customer_sk", "sr_store_sk", amount])
    group = [Field("sr_customer_sk", I64), Field("sr_store_sk", I64)]
    grouped = two_phase_agg(
        j, grouping=[fcol(f.name, I64) for f in group], group_fields=group,
        aggs=[("sum", agg("Sum", fcall("UnscaledValue", fcol(amount, MONEY),
                                       dtype=I64), I64), Field("sum", I64))])
    # the final aggregate's result expressions
    return fproject(
        grouped,
        [falias(fcol("sr_customer_sk", I64), "ctr_customer_sk"),
         falias(fcol("sr_store_sk", I64), "ctr_store_sk"),
         falias(fcall("MakeDecimal", fcol("sum", I64), dtype=TOTAL),
                "ctr_total_return")],
        Schema((Field("ctr_customer_sk", I64), Field("ctr_store_sk", I64),
                Field("ctr_total_return", TOTAL))))


def build_plan(cat, params):
    select = select_list(params)
    carried = [c for c in select if c != "c_customer_id"]
    ctr1 = ffilter(_customer_total_return(cat, params, True),
                   _not_null("ctr_total_return", TOTAL))
    # the subquery, decorrelated: avg(ctr_total_return) * 1.2 by store
    ctr2 = _keep(_customer_total_return(cat, params, False),
                 ["ctr_store_sk", "ctr_total_return"])
    by_store = two_phase_decimal_avg(
        ctr2, Field("ctr_store_sk", I64), "avg",
        fcol("ctr_total_return", TOTAL))
    threshold = fcall(
        "CheckOverflow",
        fcall("Multiply",
              fcall("PromotePrecision", fcol("avg", AVG_TOTAL)),
              fcall("PromotePrecision", flit(FACTOR, AVG_TOTAL))),
        dtype=THRESHOLD)
    by_store = ffilter(
        fproject(by_store,
                 [falias(threshold, "ctr_threshold"),
                  falias(fcol("ctr_store_sk", I64), "avg_store_sk")],
                 Schema((Field("ctr_threshold", THRESHOLD),
                         Field("avg_store_sk", I64)))),
        _not_null("ctr_threshold", THRESHOLD))
    over = fcall("GreaterThan",
                 fcall("Cast", fcol("ctr_total_return", TOTAL),
                       dtype=THRESHOLD),
                 fcol("ctr_threshold", THRESHOLD))
    j = _keep(bhj_where(ctr1, by_store, fcol("ctr_store_sk", I64),
                        fcol("avg_store_sk", I64), over),
              ["ctr_customer_sk", "ctr_store_sk"]
              + [c for c in carried if c != "ctr_store_sk"])
    st = _keep(ffilter(cat.scan("store", SCANS["store"]), _all(
        _not_null("s_state", STR),
        fcall("EqualTo", fcol("s_state", STR), flit(params["STATE"])),
        _not_null("s_store_sk", I64))), ["s_store_sk"])
    j = _keep(bhj(j, st, fcol("ctr_store_sk", I64), fcol("s_store_sk", I64)),
              ["ctr_customer_sk"] + carried)
    cu = ffilter(cat.scan("customer", SCANS["customer"]),
                 _not_null("c_customer_sk", I64))
    j = _keep(smj(j, cu, fcol("ctr_customer_sk", I64),
                  fcol("c_customer_sk", I64)), select)
    order = ["c_customer_id"] + [c for c in carried if c == "ctr_store_sk"]
    out = Schema(tuple(Field(c, _OUT_TYPES[c]) for c in select))
    return take_ordered(
        j, orders=[so(fcol(c, _OUT_TYPES[c])) for c in order], limit=100,
        project=[fcol(c, _OUT_TYPES[c]) for c in select], out=out)


def _half_up(n: int, d: int) -> int:
    """n / d over Python integers, d positive, rounded half up (away from
    zero), as java.math.BigDecimal's HALF_UP."""
    q = (2 * abs(n) + d) // (2 * d)
    return -q if n < 0 else q


def spark_threshold(total_cents: int, count: int) -> int:
    """avg * 1.2 of a store, unscaled at decimal(24,7), from the sum of its
    groups' totals (unscaled cents) and their count, with Spark's types:
    Divide(sum decimal(27,2), count decimal(20,0)) at decimal(38,13), cast
    to decimal(21,6), times 1.200000 at scale 12, rounded to scale 7."""
    q13 = _half_up(total_cents * 10 ** 11, count)
    avg6 = _half_up(q13, 10 ** 7)
    return _half_up(avg6 * 1_200_000, 10 ** 5)


def reference(read, params, dtype=np.float64, avg_dtype=None):
    """The query's text over the generated tables: numpy for the joins and
    the first sum (integers of cents), Python integers for the average,
    the product and the comparison, with Spark's roundings.  The controls
    pass `dtype=np.float32` (the sums and the average in float32) or
    `avg_dtype` (the sums exact, the average in that float type); either
    way the average is then rounded to decimal(21,6), and the product and
    the comparison are the decimal ones."""
    select = select_list(params)
    sr = read("store_returns", SCANS["store_returns"])
    dd = read("date_dim", SCANS["date_dim"]).to_pandas()
    st = read("store", SCANS["store"]).to_pandas()
    cu = read("customer", SCANS["customer"])
    days = dd[dd.d_year == int(params["YEAR"])].d_date_sk.to_numpy()
    in_year = np.isin(refmath.ints(sr["sr_returned_date_sk"]), days) \
        & ~refmath.nulls(sr["sr_returned_date_sk"])
    cents, amt_null = refmath.unscaled(sr[params["AGG_FIELD"].lower()])
    # the CTE: a null customer or store is a group key like any other
    cust = refmath.ints(sr["sr_customer_sk"], fill=-1)[in_year]
    store = refmath.ints(sr["sr_store_sk"], fill=-1)[in_year]
    valid = ~amt_null[in_year]
    codes, first = refmath.group_codes(cust, store)
    n = len(first)
    if dtype == np.float64:
        total = refmath.group_sum(np.where(valid, cents[in_year], 0), codes,
                                  n, np.int64)
        float_avg = avg_dtype
    else:
        # money as float dollars, summed in `dtype`, back to cents
        dollars = refmath.group_sum(
            np.where(valid, cents[in_year].astype(dtype) / dtype(100), 0),
            codes, n, dtype)
        total = np.floor(dollars.astype(np.float64) * 100 + 0.5) \
            .astype(np.int64)
        float_avg = dtype
    has_total = np.bincount(codes, weights=valid, minlength=n) > 0
    g_cust, g_store = cust[first], store[first]
    # the subquery: per store, over its groups that have a total
    in_avg = has_total & (g_store >= 0)
    stores, s_code = np.unique(g_store[in_avg], return_inverse=True)
    counts = np.bincount(s_code, minlength=len(stores))
    if float_avg is None:
        sums = np.zeros(len(stores), dtype=object)
        np.add.at(sums, s_code, total[in_avg].astype(object))
        thr = [spark_threshold(int(s), int(c)) for s, c in zip(sums, counts)]
    else:
        money = (dollars if dtype != np.float64
                 else total.astype(float_avg) / float_avg(100))
        sums = np.zeros(len(stores), dtype=float_avg)
        np.add.at(sums, s_code, money[in_avg])
        avg6 = np.floor((sums / counts.astype(float_avg))
                        .astype(np.float64) * 1e6 + 0.5)
        thr = [_half_up(int(a) * 1_200_000, 10 ** 5) for a in avg6]
    thr = np.array(thr, dtype=object)
    # ctr1: a group with a total, at a store, is one of its store's average
    at = np.searchsorted(stores, g_store).clip(max=max(len(stores) - 1, 0))
    over = np.zeros(n, dtype=bool)
    over[in_avg] = total[in_avg].astype(object) * 10 ** 5 > thr[at[in_avg]]
    # store of the state, then customer
    tn = st[st.s_state == params["STATE"]].s_store_sk.to_numpy()
    c_sk = refmath.ints(cu["c_customer_sk"], fill=-1)
    keep = over & np.isin(g_store, tn) & (g_cust >= 0) & np.isin(g_cust, c_sk)
    rows = np.flatnonzero(keep)
    by_sk = np.argsort(c_sk)
    ids = cu["c_customer_id"].combine_chunks().take(
        pa.array(by_sk[np.searchsorted(c_sk[by_sk], g_cust[rows])])) \
        .to_numpy(zero_copy_only=False).astype(str)
    order = np.lexsort((g_store[rows], ids))[:100]
    rows, ids = rows[order], ids[order]
    cols = {"c_customer_id": pa.array(ids, pa.string()),
            "ctr_store_sk": pa.array(g_store[rows], pa.int64()),
            "ctr_total_return": decimal_array(total[rows], 17, 2),
            "ctr_threshold": decimal_array(
                np.array([int(x) for x in thr[at[rows]]], np.int64), 24, 7)}
    return pa.table({c: cols[c] for c in select})


def kernel_least_bytes(groups: int, stores: int) -> dict:
    """The least bytes each 128-bit kernel of the plan has to move, for
    its share of the roofline (PERF.md section 5, by hand from
    `python -m auron_tpu.trace device`): `groups` (customer, store)
    totals enter the store averages, `stores` averages leave them, and
    `groups` totals meet their store's threshold.  A total is 8 bytes and
    a validity byte, a wide decimal 16 and one, a count 8, a store key 8."""
    return {
        # partial and final: totals in, (sum, count) out by store, twice
        "dec128/sum": groups * (9 + 8) + 2 * stores * (17 + 8)
        + stores * (17 + 8 + 8),
        # sum and count in, the average out
        "dec128/div": stores * (17 + 8 + 17),
        # the average in, the threshold out
        "dec128/mul": stores * (17 + 17),
        # a total cast and compared with a threshold: the flag out
        "dec128/cast": groups * (9 + 17),
        "dec128/cmp": groups * (17 + 17 + 1),
    }

"""TPC-DS query 7 (v3 spec, Appendix B query7.tpl): per item, the average
quantity, list price, coupon amount and sales price of store sales to one
demographic under a promotion-channel predicate in one year; first 100
items.

    select i_item_id, avg(ss_quantity) agg1, avg(ss_list_price) agg2,
           avg(ss_coupon_amt) agg3, avg(ss_sales_price) agg4
    from store_sales, customer_demographics, date_dim, item, promotion
    where ss_sold_date_sk = d_date_sk and ss_item_sk = i_item_sk
      and ss_cdemo_sk = cd_demo_sk and ss_promo_sk = p_promo_sk
      and cd_gender = '[GEN]' and cd_marital_status = '[MS]'
      and cd_education_status = '[ES]'
      and (p_channel_email = 'N' or p_channel_event = 'N')
      and d_year = [YEAR]
    group by i_item_id order by i_item_id limit 100

`params` are the template's substitution parameters; the traffic file gives
them (the spec's qualification values are GEN M, MS S, ES College, YEAR
2000).  The plan is the physical plan Spark 3 hands over for this text: four
broadcast hash joins with each build side filtered and projected to its key
first, a projection after each join, a two-phase aggregate, and, Spark's
DecimalAggregates rule having rewritten avg over decimal(7,2), averages of
the unscaled values as doubles, divided by 100.0 and cast to decimal(11,6)
in the final aggregate's result expressions.
"""

import numpy as np
import pyarrow as pa

from benchmarks.harness import refmath
from benchmarks.harness.plans import (F64, I32, I64, STR, DataType, Field,
                                      Schema, agg, bhj, falias, fcall, fcol,
                                      ffilter, flit, fproject, so,
                                      take_ordered, two_phase_agg)
from benchmarks.tables._common import decimal_array

MONEY = DataType.decimal(7, 2)
AVG_MONEY = DataType.decimal(11, 6)
# table -> the columns the plan's scans project: what is generated, what the
# reference reads, and what the roofline's bytes count
SCANS = {
    "store_sales": ["ss_sold_date_sk", "ss_item_sk", "ss_cdemo_sk",
                    "ss_promo_sk", "ss_quantity", "ss_list_price",
                    "ss_sales_price", "ss_coupon_amt"],
    "customer_demographics": ["cd_demo_sk", "cd_gender",
                              "cd_marital_status", "cd_education_status"],
    "date_dim": ["d_date_sk", "d_year"],
    "item": ["i_item_sk", "i_item_id"],
    "promotion": ["p_promo_sk", "p_channel_email", "p_channel_event"],
}
_PRICES = ("ss_list_price", "ss_coupon_amt", "ss_sales_price")
# how far a timed answer may lie from the reference: PERF.md section 2 has
# the readings each limit was set from
LIMITS = {"rows_differ": 0, "float_rel_gap": 1e-10}


def _all(*conds):
    out = conds[0]
    for c in conds[1:]:
        out = fcall("And", out, c)
    return out


def _eq(name, dtype, value):
    return fcall("EqualTo", fcol(name, dtype), flit(value))


def _not_null(name, dtype):
    return fcall("IsNotNull", fcol(name, dtype))


def _keys_of(node, key):
    """A build side projected to its join key, as Spark prunes it."""
    return fproject(node, [fcol(key, I64)], Schema((Field(key, I64),)))


def _join_then_keep(probe, build, probe_key, build_key, keep):
    joined = bhj(probe, build, fcol(probe_key, I64), fcol(build_key, I64))
    fields = {f.name: f for f in joined.output.fields}
    return fproject(joined, [fcol(c, fields[c].dtype) for c in keep],
                    Schema(tuple(fields[c] for c in keep)))


def build_plan(cat, params):
    ss = ffilter(cat.scan("store_sales", SCANS["store_sales"]), _all(
        _not_null("ss_cdemo_sk", I64), _not_null("ss_sold_date_sk", I64),
        _not_null("ss_item_sk", I64), _not_null("ss_promo_sk", I64)))
    cd = _keys_of(ffilter(
        cat.scan("customer_demographics", SCANS["customer_demographics"]),
        _all(_eq("cd_gender", STR, params["GEN"]),
             _eq("cd_marital_status", STR, params["MS"]),
             _eq("cd_education_status", STR, params["ES"]))), "cd_demo_sk")
    year = _eq("d_year", I32, int(params["YEAR"]))
    dd = _keys_of(ffilter(cat.scan("date_dim", SCANS["date_dim"],
                                   pushed_filters=[year]), year),
                  "d_date_sk")
    it = cat.scan("item", SCANS["item"])
    pr = _keys_of(ffilter(cat.scan("promotion", SCANS["promotion"]), fcall(
        "Or", _eq("p_channel_email", STR, "N"),
        _eq("p_channel_event", STR, "N"))), "p_promo_sk")
    measures = ["ss_quantity", "ss_list_price", "ss_sales_price",
                "ss_coupon_amt"]
    j = _join_then_keep(ss, cd, "ss_cdemo_sk", "cd_demo_sk",
                        ["ss_sold_date_sk", "ss_item_sk", "ss_promo_sk"]
                        + measures)
    j = _join_then_keep(j, dd, "ss_sold_date_sk", "d_date_sk",
                        ["ss_item_sk", "ss_promo_sk"] + measures)
    j = _join_then_keep(j, it, "ss_item_sk", "i_item_sk",
                        ["ss_promo_sk"] + measures + ["i_item_id"])
    j = _join_then_keep(j, pr, "ss_promo_sk", "p_promo_sk",
                        measures + ["i_item_id"])
    aggs = [("agg1", agg("Average", fcall(
        "Cast", fcol("ss_quantity", I32), dtype=F64), F64),
        Field("agg1", F64))]
    for i, price in enumerate(_PRICES, start=2):
        aggs.append((f"avg{i}", agg("Average", fcall("Cast", fcall(
            "UnscaledValue", fcol(price, MONEY), dtype=I64), dtype=F64),
            F64), Field(f"avg{i}", F64)))
    grouped = two_phase_agg(
        j, grouping=[fcol("i_item_id", STR)],
        group_fields=[Field("i_item_id", STR)], aggs=aggs)
    # the final aggregate's result expressions
    out = Schema((Field("i_item_id", STR), Field("agg1", F64))
                 + tuple(Field(f"agg{i}", AVG_MONEY) for i in (2, 3, 4)))
    project = [fcol("i_item_id", STR), fcol("agg1", F64)] + [
        falias(fcall("Cast", fcall(
            "Divide", fcol(f"avg{i}", F64), flit(100.0), dtype=F64),
            dtype=AVG_MONEY), f"agg{i}") for i in (2, 3, 4)]
    return take_ordered(grouped, orders=[so(fcol("i_item_id", STR))],
                        limit=100, project=project, out=out)


def reference(read, params, dtype=np.float64):
    """The query's text over the generated tables.  In float64, the
    precision the configuration states, a decimal average is exact: the sum
    of cents over the count, rounded half up at the sixth place, which is
    what the double arithmetic of Spark's plan gives wherever the quotient
    is not within 1e-9 of a tie.  The control passes float32."""
    ss = read("store_sales", SCANS["store_sales"])
    cd = read("customer_demographics", SCANS["customer_demographics"]) \
        .to_pandas()
    dd = read("date_dim", SCANS["date_dim"]).to_pandas()
    it = read("item", SCANS["item"]).to_pandas()
    pr = read("promotion", SCANS["promotion"]).to_pandas()
    cd = cd[(cd.cd_gender == params["GEN"])
            & (cd.cd_marital_status == params["MS"])
            & (cd.cd_education_status == params["ES"])]
    dd = dd[dd.d_year == int(params["YEAR"])]
    pr = pr[(pr.p_channel_email == "N") | (pr.p_channel_event == "N")]
    # a null key (filled with 0 here) joins nothing
    keep = np.isin(refmath.ints(ss["ss_cdemo_sk"]), cd.cd_demo_sk) \
        & np.isin(refmath.ints(ss["ss_sold_date_sk"]), dd.d_date_sk) \
        & np.isin(refmath.ints(ss["ss_promo_sk"]), pr.p_promo_sk) \
        & np.isin(refmath.ints(ss["ss_item_sk"]), it.i_item_sk)
    item_id = it.set_index("i_item_sk").i_item_id
    ids, codes = np.unique(
        item_id.loc[refmath.ints(ss["ss_item_sk"])[keep]].to_numpy(str),
        return_inverse=True)
    n = len(ids)

    def sum_and_count(values, null):
        valid = ~null[keep]
        return (refmath.group_sum(np.where(valid, values[keep], 0), codes,
                                  n, dtype),
                np.bincount(codes, weights=valid, minlength=n)
                .astype(np.int64))

    qty_sum, qty_n = sum_and_count(
        refmath.ints(ss["ss_quantity"]).astype(dtype),
        refmath.nulls(ss["ss_quantity"]))
    with np.errstate(invalid="ignore", divide="ignore"):
        agg1 = (qty_sum / qty_n.astype(dtype)).astype(np.float64)
    cols = {"i_item_id": pa.array(ids[:100], pa.string()),
            "agg1": pa.array(agg1[:100], mask=(qty_n == 0)[:100])}
    for i, price in enumerate(_PRICES, start=2):
        cents, null = refmath.unscaled(ss[price])
        if dtype == np.float64:
            total, count = sum_and_count(cents, null)
            avg6 = refmath.half_up(total.astype(np.int64) * 10_000,
                                   np.maximum(count, 1))
        else:
            total, count = sum_and_count(
                cents.astype(dtype) / dtype(100), null)
            avg = total / np.maximum(count, 1).astype(dtype)
            avg6 = np.floor(avg.astype(np.float64) * 1e6 + 0.5) \
                .astype(np.int64)
        cols[f"agg{i}"] = decimal_array(avg6[:100], 11, 6,
                                        (count == 0)[:100])
    return pa.table(cols)

"""store_sales: 2,880,404 rows at SF1 (TPC-DS v3 table 3-2), made ticket by
ticket as dsdgen's w_store_sales.c makes them: a ticket of 8 to 16 line
items shares its date, time, customer, demographics, address and store; its
items are distinct (a walk through one permutation of the business keys from
a random start) and each is the item revision in force on the sale date;
money is decimal(7,2), priced as dsdgen's pricing.c does (wholesale 1.00 to
100.00, a mark-up of 0 to 200 % to the list price, a discount of 0 to 100 %
to the sales price, a coupon on a fifth of the lines).  Sales are seasonal
over 1998-01-02 to 2003-01-02: the spec's three zones (January to July low,
August to October medium, November and December high), uniform inside a
zone.  9 % of the rows carry nulls, each nullable column of such a row with
probability 1/2 (4.5 % a column, as in dsdgen's output).  The configuration
file lists which of these numbers the spec gives and which are assumed.

Tickets, dates, keys and null masks come from `rng` (the configuration's
database); quantity and pricing from `ctx.amounts_rng` (the run's seed).
"""

import numpy as np
import pyarrow as pa

from . import date_dim, item

CHUNKS = 4
FIRST_DAY, LAST_DAY = "1998-01-02", "2003-01-02"
# relative weight of one day of each month (zone 1, 2, 3)
_MONTH_WEIGHT = np.array([1, 1, 1, 1, 1, 1, 1, 1.5, 1.5, 1.5, 2.5, 2.5])
N_HOUSEHOLD_DEMOGRAPHICS = 7_200
N_TIME = 86_400
NULL_ROW_SHARE = 0.09
_KEYS = ("ss_sold_date_sk", "ss_sold_time_sk", "ss_customer_sk",
         "ss_cdemo_sk", "ss_hdemo_sk", "ss_addr_sk", "ss_store_sk",
         "ss_promo_sk")
_MONEY = ("ss_wholesale_cost", "ss_list_price", "ss_sales_price",
          "ss_ext_discount_amt", "ss_ext_sales_price",
          "ss_ext_wholesale_cost", "ss_ext_list_price", "ss_ext_tax",
          "ss_coupon_amt", "ss_net_paid", "ss_net_paid_inc_tax",
          "ss_net_profit")
_NEVER_NULL = ("ss_item_sk", "ss_ticket_number")
COLUMNS = _KEYS[:2] + ("ss_item_sk",) + _KEYS[2:] + (
    "ss_ticket_number", "ss_quantity") + _MONEY


def _tickets(n, rng):
    """Ticket number (0-based) of each of the n rows: 8 to 16 rows a
    ticket, the last ticket cut where the table ends."""
    sizes = rng.integers(8, 17, n // 8 + 1)
    return np.repeat(np.arange(len(sizes)), sizes)[:n]


def _sale_days(n_tickets, rng):
    days = np.arange(np.datetime64(FIRST_DAY), np.datetime64(LAST_DAY) + 1)
    weight = _MONTH_WEIGHT[days.astype("datetime64[M]").astype(int) % 12]
    return rng.choice(days, n_tickets, p=weight / weight.sum())


def _item_revisions(n, ticket, day_of_row, rng, n_item):
    """ss_item_sk: distinct business keys within a ticket, each as the
    revision in force on the sale date (a key's second revision starts
    2000-10-27 if it has two, 1999-10-28 and 2001-10-27 if three)."""
    first, count = item.key_rows(n_item)
    walk = rng.permutation(len(first))
    start = rng.integers(0, len(first), int(ticket[-1]) + 1)
    line = np.arange(n) - np.searchsorted(ticket, ticket)   # 0.. in ticket
    key = walk[(start[ticket] + line) % len(first)]
    revs = count[key]
    two = (day_of_row >= np.datetime64("2000-10-27")).astype(np.int64)
    three = (day_of_row >= np.datetime64("1999-10-28")).astype(np.int64) \
        + (day_of_row >= np.datetime64("2001-10-27")).astype(np.int64)
    revision = np.where(revs == 2, two, np.where(revs == 3, three, 0))
    return first[key] + np.minimum(revision, revs - 1) + 1


def generate(n, rng, ctx, columns=None):
    from benchmarks.harness.refmath import half_up
    from ._common import decimal_array, int_array
    ticket = _tickets(n, rng)
    n_tickets = int(ticket[-1]) + 1
    day = _sale_days(n_tickets, rng)

    def per_ticket(hi, lo=1):
        return rng.integers(lo, hi + 1, n_tickets)[ticket].astype(np.int64)

    v = {
        "ss_sold_date_sk": (date_dim.FIRST_SK + (
            day - np.datetime64("1900-01-02")).astype(np.int64))[ticket],
        "ss_sold_time_sk": per_ticket(N_TIME - 1, 0),
        "ss_customer_sk": per_ticket(ctx.rows("customer")),
        "ss_cdemo_sk": per_ticket(ctx.rows("customer_demographics")),
        "ss_hdemo_sk": per_ticket(N_HOUSEHOLD_DEMOGRAPHICS),
        "ss_addr_sk": per_ticket(ctx.rows("customer_address")),
        "ss_store_sk": per_ticket(ctx.rows("store")),
        "ss_item_sk": _item_revisions(n, ticket, day[ticket], rng,
                                      ctx.rows("item")),
        "ss_promo_sk": rng.integers(1, ctx.rows("promotion") + 1, n),
        "ss_ticket_number": ticket.astype(np.int64) + 1,
    }
    # quantity and pricing, in cents: the run's own
    amt = ctx.amounts_rng("store_sales")
    qty = amt.integers(1, 101, n)
    wholesale = amt.integers(100, 10_001, n)
    list_price = half_up(wholesale * (100 + amt.integers(0, 201, n)), 100)
    sales = half_up(list_price * (100 - amt.integers(0, 101, n)), 100)
    ext_sales = sales * qty
    coupon = np.where(amt.integers(1, 101, n) <= 20,
                      half_up(ext_sales * amt.integers(0, 101, n), 100), 0)
    net_paid = ext_sales - coupon
    tax = half_up(net_paid * amt.integers(0, 10, n), 100)
    v.update({
        "ss_quantity": qty.astype(np.int32),
        "ss_wholesale_cost": wholesale, "ss_list_price": list_price,
        "ss_sales_price": sales,
        "ss_ext_discount_amt": (list_price - sales) * qty,
        "ss_ext_sales_price": ext_sales,
        "ss_ext_wholesale_cost": wholesale * qty,
        "ss_ext_list_price": list_price * qty,
        "ss_ext_tax": tax, "ss_coupon_amt": coupon, "ss_net_paid": net_paid,
        "ss_net_paid_inc_tax": net_paid + tax,
        "ss_net_profit": net_paid - wholesale * qty,
    })
    null_row = rng.random(n) < NULL_ROW_SHARE
    out = {}
    for name in COLUMNS:
        # every column draws its mask, asked for or not: a column is the
        # same whichever query's scans name it
        mask = None if name in _NEVER_NULL \
            else null_row & (rng.random(n) < 0.5)
        if columns is not None and name not in columns:
            continue
        out[name] = decimal_array(v[name], 7, 2, mask) if name in _MONEY \
            else int_array(v[name], mask)
    return pa.table(out)

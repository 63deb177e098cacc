"""store_returns: 2,875,432 rows at SF10, 287,514 at SF1 (TPC-DS v3 table
3-2), made as dsdgen's w_store_returns.c makes them: a return belongs to a
line of a store_sales ticket (about one line in ten is returned), so
returns of one ticket share its customer, its store and its sale date; the
item comes back 1 to 90 days after the sale, in 80 % of returns by the
ticket's customer and otherwise by another one, at the ticket's store, in
a quantity of 1 up to the quantity sold; sr_return_amt is the returned
quantity times the line's sales price, priced as store_sales prices a
line.  9 % of the rows carry nulls, each nullable column of such a row with
probability 1/2 (4.5 % a column, as in dsdgen's output).

The table does not read store_sales' rows: the tickets returned from are
drawn (a ticket number out of store_sales' own count of tickets, and that
ticket's customer, store and sale date as store_sales draws them), so that
writing 28.8M sales to derive 2.9M returns is not part of every set-up.
The configuration file lists which of these numbers the spec gives and
which are assumed.

Tickets, dates, keys and null masks come from `rng` (the configuration's
database); quantities and pricing from `ctx.amounts_rng` (the run's seed).
"""

import numpy as np
import pyarrow as pa

from . import date_dim, store_sales

CHUNKS = 4
N_REASON = 45            # SF10's reason table (35 at SF1)
OTHER_CUSTOMER_SHARE = 0.2
_KEYS = ("sr_returned_date_sk", "sr_return_time_sk", "sr_customer_sk",
         "sr_cdemo_sk", "sr_hdemo_sk", "sr_addr_sk", "sr_store_sk",
         "sr_reason_sk")
_MONEY = ("sr_return_amt", "sr_return_tax", "sr_return_amt_inc_tax",
          "sr_fee", "sr_return_ship_cost", "sr_refunded_cash",
          "sr_reversed_charge", "sr_store_credit", "sr_net_loss")
_NEVER_NULL = ("sr_item_sk", "sr_ticket_number")
COLUMNS = _KEYS[:2] + ("sr_item_sk",) + _KEYS[2:] + (
    "sr_ticket_number", "sr_return_quantity") + _MONEY


def generate(n, rng, ctx, columns=None):
    from benchmarks.harness.refmath import half_up
    from ._common import decimal_array, int_array
    # the tickets returned from, ascending like the fact table they
    # follow: a ticket with several returned lines appears several times
    n_tickets = max(1, ctx.rows("store_sales") // 12)
    ticket = np.sort(rng.integers(0, n_tickets, n))
    of_ticket, row_ticket = np.unique(ticket, return_inverse=True)
    k = len(of_ticket)
    sale_day = store_sales._sale_days(k, rng)
    t_customer = rng.integers(1, ctx.rows("customer") + 1, k)
    t_store = rng.integers(1, ctx.rows("store") + 1, k)
    returned = sale_day[row_ticket] + rng.integers(1, 91, n)
    other = rng.random(n) < OTHER_CUSTOMER_SHARE
    v = {
        "sr_returned_date_sk": date_dim.FIRST_SK + (
            returned - np.datetime64("1900-01-02")).astype(np.int64),
        "sr_return_time_sk": rng.integers(0, store_sales.N_TIME, n),
        "sr_item_sk": rng.integers(1, ctx.rows("item") + 1, n),
        "sr_customer_sk": np.where(
            other, rng.integers(1, ctx.rows("customer") + 1, n),
            t_customer[row_ticket]),
        "sr_cdemo_sk": rng.integers(
            1, ctx.rows("customer_demographics") + 1, n),
        "sr_hdemo_sk": rng.integers(
            1, store_sales.N_HOUSEHOLD_DEMOGRAPHICS + 1, n),
        "sr_addr_sk": rng.integers(1, ctx.rows("customer_address") + 1, n),
        "sr_store_sk": t_store[row_ticket],
        "sr_reason_sk": rng.integers(1, N_REASON + 1, n),
        "sr_ticket_number": ticket.astype(np.int64) + 1,
    }
    # what came back and what it was worth, in cents: the run's own
    amt = ctx.amounts_rng("store_returns")
    sold = amt.integers(1, 101, n)
    qty = amt.integers(1, sold + 1)
    wholesale = amt.integers(100, 10_001, n)
    list_price = half_up(wholesale * (100 + amt.integers(0, 201, n)), 100)
    sales = half_up(list_price * (100 - amt.integers(0, 101, n)), 100)
    return_amt = sales * qty
    tax = half_up(return_amt * amt.integers(0, 10, n), 100)
    fee = amt.integers(50, 10_001, n)
    ship = half_up(list_price * amt.integers(0, 101, n), 100) * qty
    total = return_amt + tax
    cash = half_up(total * amt.integers(0, 101, n), 100)
    charge = half_up((total - cash) * amt.integers(0, 101, n), 100)
    v.update({
        "sr_return_quantity": qty.astype(np.int32),
        "sr_return_amt": return_amt, "sr_return_tax": tax,
        "sr_return_amt_inc_tax": total, "sr_fee": fee,
        "sr_return_ship_cost": ship, "sr_refunded_cash": cash,
        "sr_reversed_charge": charge,
        "sr_store_credit": total - cash - charge,
        "sr_net_loss": total + fee + ship - cash,
    })
    null_row = rng.random(n) < store_sales.NULL_ROW_SHARE
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

"""customer: 500,000 rows at SF10, 100,000 at SF1 (TPC-DS v3 table 3-2).
One row a customer (no revisions): c_customer_sk counts from 1 and
c_customer_id is its 16-letter business key, as dsdgen's mk_bkey writes it;
the demographics and address keys are uniform over their dimensions.  Only
the columns asked for are built: the strings of 500,000 rows are what a
set-up would otherwise spend its time on."""

import numpy as np
import pyarrow as pa

CHUNKS = 2
N_HOUSEHOLD_DEMOGRAPHICS = 7_200
COLUMNS = ("c_customer_sk", "c_customer_id", "c_current_cdemo_sk",
           "c_current_hdemo_sk", "c_current_addr_sk", "c_birth_year",
           "c_preferred_cust_flag")


def generate(n, rng, ctx, columns=None):
    from ._common import business_keys, choice_strings
    idx = np.arange(n)
    # every column draws, asked for or not: a column is the same whichever
    # query's scans name it
    cdemo = rng.integers(1, ctx.rows("customer_demographics") + 1, n)
    hdemo = rng.integers(1, N_HOUSEHOLD_DEMOGRAPHICS + 1, n)
    addr = rng.integers(1, ctx.rows("customer_address") + 1, n)
    birth = rng.integers(1924, 1993, n).astype(np.int32)
    flag = rng.integers(0, 2, n)
    make = {
        "c_customer_sk": lambda: (idx + 1).astype(np.int64),
        "c_customer_id": lambda: business_keys(idx + 1),
        "c_current_cdemo_sk": lambda: cdemo,
        "c_current_hdemo_sk": lambda: hdemo,
        "c_current_addr_sk": lambda: addr,
        "c_birth_year": lambda: birth,
        "c_preferred_cust_flag": lambda: choice_strings(("N", "Y"), flag),
    }
    return pa.table({name: make[name]() for name in COLUMNS
                     if columns is None or name in columns})

"""store: 102 rows at SF10, 12 at SF1 (TPC-DS v3 table 3-2).  A slowly
changing dimension as dsdgen makes one (two revisions a business key: half
as many s_store_id as rows).  dsdgen draws a store's county, and with it
its state, from a distribution that at small scales reaches few states:
SF1's twelve stores all lie in TN.  Which states SF10's 102 stores reach
is assumed (the configuration file says so): nine states, TN with four
parts in ten, the first store in TN and the second not, so that
`s_state = 'TN'` keeps some stores and drops others at every size."""

import numpy as np
import pyarrow as pa

CHUNKS = 1
STATES = ("TN", "SD", "AL", "GA", "OH", "MN", "IA", "MO", "IN")
_WEIGHTS = np.array([0.4] + [0.075] * 8)


def generate(n, rng, ctx, columns=None):
    from ._common import business_keys, choice_strings, decimal_array
    idx = np.arange(n)
    state = rng.choice(len(STATES), n, p=_WEIGHTS)
    state[:1] = 0
    state[1:2] = 1 + state[1:2] % (len(STATES) - 1)
    return pa.table({
        "s_store_sk": (idx + 1).astype(np.int64),
        "s_store_id": business_keys(idx // 2 + 1),
        "s_number_employees": rng.integers(200, 301, n).astype(np.int32),
        "s_floor_space": rng.integers(5_000_000, 10_000_001, n)
        .astype(np.int32),
        "s_market_id": rng.integers(1, 11, n).astype(np.int32),
        "s_state": choice_strings(STATES, state),
        "s_gmt_offset": decimal_array(
            np.where(state < 5, -500, -600), 5, 2),
        "s_tax_percentage": decimal_array(rng.integers(0, 12, n), 5, 2),
    })

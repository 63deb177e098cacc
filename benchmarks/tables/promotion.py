"""promotion: 300 rows at SF1 (TPC-DS v3 table 3-2).  dsdgen draws nine
channel flags as the bits of one number and shifts the wrong way after the
first, so p_channel_dmail is Y for half of the rows and every other channel
flag is N in every row; the generator keeps that, because it decides what
q7's (p_channel_email = 'N' or p_channel_event = 'N') lets through: all."""

import numpy as np
import pyarrow as pa

CHUNKS = 1
_YN = ("N", "Y")


def generate(n, rng, ctx, columns=None):
    from ._common import business_keys, choice_strings, decimal_array
    none = np.zeros(n, dtype=np.int32)
    return pa.table({
        "p_promo_sk": np.arange(n, dtype=np.int64) + 1,
        "p_promo_id": business_keys(np.arange(n) + 1),
        "p_cost": decimal_array(np.full(n, 100_000), 15, 2),
        "p_response_target": np.ones(n, dtype=np.int32),
        "p_channel_dmail": choice_strings(_YN, rng.integers(0, 2, n)),
        "p_channel_email": choice_strings(_YN, none),
        "p_channel_catalog": choice_strings(_YN, none),
        "p_channel_tv": choice_strings(_YN, none),
        "p_channel_radio": choice_strings(_YN, none),
        "p_channel_press": choice_strings(_YN, none),
        "p_channel_event": choice_strings(_YN, none),
        "p_channel_demo": choice_strings(_YN, none),
        "p_discount_active": choice_strings(_YN, none),
    })

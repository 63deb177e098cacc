"""item: 18,000 rows at SF1 (TPC-DS v3 table 3-2).  A slowly changing
dimension as dsdgen makes one: of every six rows the first is a business
key's only revision, the next two are the two revisions of a second key and
the last three the three revisions of a third, so half as many i_item_id as
rows; revisions of one key share the i_item_id of its first row."""

import numpy as np
import pyarrow as pa

CHUNKS = 1
# first row of the key each row of a block of six belongs to, from that row
_BACK = np.array([0, 0, 1, 0, 1, 2])
# revision number within its key, and how many the key has
_REVISION = np.array([0, 0, 1, 0, 1, 2])
_REVISIONS = np.array([1, 2, 2, 3, 3, 3])
_CATEGORIES = ("Books", "Home", "Electronics", "Jewelry", "Music",
               "Shoes", "Sports", "Women", "Men", "Children")


def key_rows(n):
    """Row index (0-based) of each business key's first revision, and how
    many revisions it has among the n rows."""
    idx = np.arange(n)
    first = idx[_REVISION[idx % 6] == 0]
    count = np.minimum(_REVISIONS[first % 6], n - first)
    return first, count


def generate(n, rng, ctx, columns=None):
    from ._common import business_keys, choice_strings, decimal_array
    idx = np.arange(n)
    first_row = idx - _BACK[idx % 6]
    price = rng.integers(9, 10_000, n)              # 0.09 .. 99.99
    category = rng.integers(0, len(_CATEGORIES), n)
    return pa.table({
        "i_item_sk": (idx + 1).astype(np.int64),
        "i_item_id": business_keys(first_row + 1),
        "i_current_price": decimal_array(price, 7, 2),
        "i_wholesale_cost": decimal_array(
            price * rng.integers(50, 90, n) // 100, 7, 2),
        "i_category_id": (category + 1).astype(np.int32),
        "i_category": choice_strings(_CATEGORIES, category),
        "i_manager_id": rng.integers(1, 101, n).astype(np.int32),
        "i_manufact_id": rng.integers(1, 1001, n).astype(np.int32),
    })

"""date_dim: 73,049 days, 1900-01-02 to 2100-01-01, at every scale (TPC-DS
v3 table 3-2).  d_date_sk is the Julian day number, as dsdgen has it
(2415022 is 1900-01-02; 2451545 is 2000-01-01).  The table depends on
neither the seed nor the scale."""

import numpy as np
import pyarrow as pa

CHUNKS = 1
FIRST_SK = 2415022
_FIRST_DAY = np.datetime64("1900-01-02")
_DAY_NAMES = ("Sunday", "Monday", "Tuesday", "Wednesday", "Thursday",
              "Friday", "Saturday")


def date_sk(day) -> int:
    return FIRST_SK + int((np.datetime64(day) - _FIRST_DAY)
                          / np.timedelta64(1, "D"))


def generate(n, rng, ctx, columns=None):
    from ._common import choice_strings
    days = _FIRST_DAY + np.arange(n)
    year = days.astype("datetime64[Y]").astype(np.int64) + 1970
    month0 = days.astype("datetime64[M]").astype(np.int64) % 12
    dom = (days - days.astype("datetime64[M]")).astype(np.int64) + 1
    dow = (days.astype(np.int64) + 4) % 7      # 1970-01-01 was a Thursday
    return pa.table({
        "d_date_sk": np.arange(n, dtype=np.int64) + FIRST_SK,
        "d_date": pa.array(days.astype("datetime64[D]").astype(np.int32),
                           type=pa.date32()),
        "d_year": year.astype(np.int32),
        "d_moy": (month0 + 1).astype(np.int32),
        "d_dom": dom.astype(np.int32),
        "d_qoy": (month0 // 3 + 1).astype(np.int32),
        "d_dow": dow.astype(np.int32),
        "d_month_seq": ((year - 1900) * 12 + month0).astype(np.int32),
        "d_day_name": choice_strings(_DAY_NAMES, dow),
    })

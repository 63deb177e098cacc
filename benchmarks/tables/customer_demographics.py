"""customer_demographics: 1,920,800 rows at every scale (TPC-DS v3 table
3-2): the cross product of its attribute domains, cd_demo_sk counting
through them with the first attribute fastest, as dsdgen does.  Depends on
neither the seed nor the scale.  A rehearsal's shorter table is its first
rows."""

import numpy as np
import pyarrow as pa

CHUNKS = 2
GENDER = ("M", "F")
MARITAL_STATUS = ("M", "S", "D", "W", "U")
EDUCATION_STATUS = ("Primary", "Secondary", "College", "2 yr Degree",
                    "4 yr Degree", "Advanced Degree", "Unknown")
CREDIT_RATING = ("Good", "Low Risk", "High Risk", "Unknown")


def generate(n, rng, ctx, columns=None):
    from ._common import choice_strings
    k = np.arange(n, dtype=np.int64)
    cols = {"cd_demo_sk": k + 1}
    for name, domain in (("cd_gender", GENDER),
                         ("cd_marital_status", MARITAL_STATUS),
                         ("cd_education_status", EDUCATION_STATUS)):
        cols[name] = choice_strings(domain, k % len(domain))
        k = k // len(domain)
    cols["cd_purchase_estimate"] = ((k % 20 + 1) * 500).astype(np.int32)
    k = k // 20
    cols["cd_credit_rating"] = choice_strings(CREDIT_RATING, k % 4)
    k = k // 4
    for name in ("cd_dep_count", "cd_dep_employed_count",
                 "cd_dep_college_count"):
        cols[name] = (k % 7).astype(np.int32)
        k = k // 7
    return pa.table(cols)

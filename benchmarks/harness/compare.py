"""The comparison that decides `correct`: the tables the timed executes
returned against the query's plain reference.  Imports nothing of the
program.

Two numbers per table, each with a limit of its own (the query's `LIMITS`):
`rows_differ`, rows whose exact columns (keys, strings, decimals) differ,
that hold a null on one side alone, or that one side lacks; and
`float_rel_gap`, the widest |got - want| / |want| over the float columns.
Rows are compared in order: the plans order their output by keys that are
unique.
"""

from __future__ import annotations

import math
from typing import Dict

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc


def compare_tables(got: pa.Table, want: pa.Table) -> Dict[str, float]:
    if got.schema.names != want.schema.names:
        return {"rows_differ": max(got.num_rows, want.num_rows, 1),
                "float_rel_gap": math.inf}
    n = min(got.num_rows, want.num_rows)
    differ = np.zeros(n, dtype=bool)
    gap = 0.0
    for name in want.schema.names:
        g = got[name].combine_chunks().slice(0, n)
        w = want[name].combine_chunks().slice(0, n)
        if pa.types.is_floating(w.type):
            if not pa.types.is_floating(g.type):
                return {"rows_differ": int(differ.sum()) + abs(
                    got.num_rows - want.num_rows), "float_rel_gap": math.inf}
            # a null on one side alone is a row that differs; the gap is
            # read where both sides hold a number
            g_null = np.asarray(g.is_null())
            w_null = np.asarray(w.is_null())
            differ |= g_null != w_null
            both = ~(g_null | w_null)
            gv = g.fill_null(0).to_numpy(zero_copy_only=False).astype(
                np.float64)[both]
            wv = w.fill_null(0).to_numpy(zero_copy_only=False).astype(
                np.float64)[both]
            with np.errstate(invalid="ignore", divide="ignore"):
                rel = np.abs(gv - wv) / np.maximum(np.abs(wv), 1e-300)
            rel[gv == wv] = 0.0
            if len(rel):
                worst = float(np.max(rel))
                gap = math.inf if math.isnan(worst) else max(gap, worst)
        else:
            if g.type != w.type:
                g = g.cast(w.type, safe=False)
            eq = pc.equal(g, w).fill_null(False)
            both_null = pc.and_(g.is_null(), w.is_null())
            differ |= ~np.asarray(pc.or_(eq, both_null))
    return {"rows_differ": int(differ.sum()) +
            abs(got.num_rows - want.num_rows), "float_rel_gap": gap}


def worst_of(per_table):
    """The worst reading of each number over the tables compared."""
    out: Dict[str, float] = {}
    for numbers in per_table:
        for k, v in numbers.items():
            out[k] = max(out.get(k, 0), v)
    return out


def judge(numbers: Dict[str, float], limits: Dict[str, float]) -> dict:
    """Each number beside its limit, and whether all hold."""
    checks = {k: {"value": numbers[k], "limit": limits[k]} for k in limits}
    ok = all(c["value"] <= c["limit"] for c in checks.values())
    return {"ok": ok, "checks": checks}

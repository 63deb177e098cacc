"""`correct` for query 1's cell at `--rehearse-cpu` scale: the faults its
answer can have, planted under the timed path, each have to read `correct`
false — a threshold moved by one unit of its seventh place, a total by one
cent, one return row dropped, customer's file halved, the average computed
in float32 — and the lower-precision control (sums and average in float32)
is held to the same limits on the parameter set that carries the numbers
out.  `test_correct.py` covers the cell for what applies to every cell (a
sound run, a key, a row, half the fact files; `benchmarks/conftest.py`
says which of its cases assume query 7's answer).
"""

import os
from decimal import Decimal

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from benchmarks import run
from benchmarks.harness import cells, compare, refmath
from benchmarks.queries import q01

CELL = "tpcds-sf10.q01"


def drive(faults=None, seed=5):
    return run.run_cell(cells.load_cell(CELL), seed=seed, seconds=0.2,
                        traced=False, rehearse_cpu=True, faults=faults)


def _nudge(column, by):
    """The first row's `column` moved by `by`, in the tables that carry
    it (the projecting parameter set's); the template's are left alone."""
    def fault(table: pa.Table) -> pa.Table:
        if column not in table.schema.names:
            return table
        i = table.schema.get_field_index(column)
        field = table.schema.field(i)
        col = table.column(i).to_pylist()
        col[0] += by
        return table.set_column(i, field, pa.array(col, field.type))
    return fault


def drop_a_return(cat) -> None:
    """One row of store_returns never reaches the scan: a return of the
    year, with an amount, at a store of the state, so that its store's
    average (and with it every threshold of that store) moves."""
    sr = cat.tables["store_returns"]
    st = cat.read("store", q01.SCANS["store"]).to_pandas()
    dd = cat.read("date_dim", q01.SCANS["date_dim"]).to_pandas()
    tn = st[st.s_state == "TN"].s_store_sk.to_numpy()
    days = dd[dd.d_year == 2000].d_date_sk.to_numpy()
    for k, path in enumerate(sr.chunks):
        t = pq.read_table(path)
        hit = np.isin(refmath.ints(t["sr_returned_date_sk"]), days) \
            & np.isin(refmath.ints(t["sr_store_sk"]), tn) \
            & ~refmath.nulls(t["sr_return_amt"]) \
            & ~refmath.nulls(t["sr_customer_sk"])
        if hit.any():
            keep = np.ones(t.num_rows, dtype=bool)
            keep[np.flatnonzero(hit)[0]] = False
            less = os.path.join(os.path.dirname(path), f"less-{k}.parquet")
            pq.write_table(t.filter(pa.array(keep)), less)
            sr.chunks[k] = less
            return
    raise AssertionError("no return of the year at a store of the state")


def halve_customer(cat) -> None:
    chunks = cat.tables["customer"].chunks
    assert len(chunks) >= 2
    del chunks[len(chunks) // 2:]


def average_in_float32():
    """The thresholds as a float32 average gives them, put where the
    program's are: the catalog fault only lends the answer fault the files
    to compute them from."""
    held = {}

    def remember(cat) -> None:
        held["cat"] = cat

    def answer(table: pa.Table) -> pa.Table:
        if "ctr_threshold" not in table.schema.names:
            return table
        params = cells.load_cell(CELL).traffic["param_sets"][1]
        low = q01.reference(held["cat"].read, params, avg_dtype=np.float32)
        i = table.schema.get_field_index("ctr_threshold")
        return table.set_column(i, table.schema.field(i),
                                low["ctr_threshold"])
    return {"catalog_for_plan": remember, "answer": answer}


FAULTS = {
    "a-threshold-by-its-seventh-place": lambda: {
        "answer": _nudge("ctr_threshold", Decimal("1e-7"))},
    "a-total-by-one-cent": lambda: {
        "answer": _nudge("ctr_total_return", Decimal("0.01"))},
    "one-return-row-dropped": lambda: {"catalog_for_plan": drop_a_return},
    "customers-file-halved": lambda: {"catalog_for_plan": halve_customer},
    "the-average-in-float32": average_in_float32,
}


def test_a_sound_run_compares_both_parameter_sets():
    result = drive()
    assert result["correct"] is True and result["failed"] == 0
    assert result["attempted"] >= 2
    assert result["checks"]["rows_differ"] == {"value": 0, "limit": 0}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_a_planted_fault_is_not_correct(fault):
    result = drive(faults=FAULTS[fault]())
    assert result["correct"] is False
    assert result["failed"] >= 1
    assert result["checks"]["rows_differ"]["value"] >= 1


@pytest.mark.parametrize("seed", [5, 6, 2**31 + 7])
def test_the_float32_control_fails_the_limits(seed, tmp_path):
    """The reference put in the program's place with its sums and its
    average in float32 has to come out as not correct on the set that
    carries the totals and thresholds out; with the sums exact and the
    average a float64 it lands on the reference's sixth place but for a
    tie (reported in PERF.md section 2, whatever it reads)."""
    import auron_tpu  # noqa: F401
    from benchmarks.harness import datagen
    cell = cells.load_cell(CELL)
    cat = datagen.generate(str(tmp_path), q01.SCANS,
                           cell.config["rehearse_rows"],
                           cell.config["data_seed"], seed)
    params = cell.traffic["param_sets"][1]
    want = q01.reference(cat.read, params)
    control = q01.reference(cat.read, params, np.float32)
    reading = compare.compare_tables(control, want)
    assert compare.judge(reading, q01.LIMITS)["ok"] is False
    assert reading["rows_differ"] >= 50
    assert compare.judge(compare.compare_tables(want, want),
                         q01.LIMITS)["ok"] is True
    as_double = q01.reference(cat.read, params, avg_dtype=np.float64)
    assert compare.compare_tables(as_double, want)["rows_differ"] <= \
        reading["rows_differ"]

"""`correct` at `--rehearse-cpu` scale: the harness's look for a chip is
skipped and the rest of a run is driven, once sound and once with each fault
a cell can have planted under the timed path, and the lower-precision
control is held to the same limits.  A step that returns its state unchanged
and the exchange between chips do not apply: a query has no state, and no
cell runs on more than one chip.

Every cell of BENCHMARK.json is a case: a cell added later is covered
without an edit here.
"""

import json
import os

import numpy as np
import pyarrow as pa
import pyarrow.compute as pc
import pytest

from benchmarks import run
from benchmarks.harness import cells, compare

with open(os.path.join(cells.REPO_DIR, "BENCHMARK.json")) as f:
    _WORKLOADS = json.load(f)["workloads"]
CELLS = [w["name"] for w in _WORKLOADS]


def drive(cell_name, faults=None, seed=5):
    return run.run_cell(cells.load_cell(cell_name), seed=seed, seconds=0.2,
                        traced=False, rehearse_cpu=True, faults=faults)


def nudge_a_float(table: pa.Table) -> pa.Table:
    """An answer altered where it is produced: one float64 of the last row
    moved by one part in 10^9 (a float32 accumulation moves it by 10^-7)."""
    i = next(i for i, f in enumerate(table.schema)
             if pa.types.is_floating(f.type))
    col = table.column(i).to_pylist()
    row = max(r for r, v in enumerate(col) if v is not None)
    col[row] *= 1 + 1e-9
    return table.set_column(i, table.schema.field(i),
                            pa.array(col, pa.float64()))


def nudge_a_decimal(table: pa.Table) -> pa.Table:
    """One decimal of the first row moved by one unit of its last place."""
    from decimal import Decimal
    i = next(i for i, f in enumerate(table.schema)
             if pa.types.is_decimal(f.type))
    field = table.schema.field(i)
    col = table.column(i).to_pylist()
    row = next(r for r, v in enumerate(col) if v is not None)
    col[row] += Decimal(1).scaleb(-field.type.scale)
    return table.set_column(i, field, pa.array(col, field.type))


def alter_a_key(table: pa.Table) -> pa.Table:
    col = table.column(0).to_pylist()
    col[0] = col[0] + "x"
    field = table.schema.field(0)
    return table.set_column(0, field, pa.array(col, field.type))


def drop_a_row(table: pa.Table) -> pa.Table:
    return table.slice(0, table.num_rows - 1)


def leave_out_half_the_batch(cat) -> None:
    """Half of the largest scanned table's files never reach the scan: the
    program then answers over the rest."""
    biggest = max(cat.tables.values(), key=lambda t: len(t.chunks))
    assert len(biggest.chunks) >= 2
    del biggest.chunks[len(biggest.chunks) // 2:]


@pytest.mark.parametrize("cell_name", CELLS)
def test_sound_run_is_correct_and_reports_every_end_to_end_metric(cell_name):
    result = drive(cell_name)
    assert result["correct"] is True and result["failed"] == 0
    assert set(result["metrics"]) == {"query_s", "query_s.p95", "setup_s"}
    assert list(result)[-1] == "checks"
    assert result["device"] == {"platform": "cpu", "kind": "cpu", "count":
                                result["device"]["count"]}


@pytest.mark.parametrize("cell_name", CELLS)
@pytest.mark.parametrize("fault", [nudge_a_float, nudge_a_decimal,
                                   alter_a_key, drop_a_row])
def test_an_altered_answer_is_not_correct(cell_name, fault):
    result = drive(cell_name, faults={"answer": fault})
    assert result["correct"] is False
    assert result["failed"] == result["attempted"]


@pytest.mark.parametrize("cell_name", CELLS)
def test_half_the_batch_left_out_is_not_correct(cell_name):
    # the reference reads every file the generator wrote; the plan is built
    # from the catalog after the fault, so the program scans half of them
    result = drive(cell_name, faults={"catalog_for_plan":
                                      leave_out_half_the_batch})
    assert result["correct"] is False


@pytest.mark.parametrize("cell_name", CELLS)
@pytest.mark.parametrize("seed", [5, 6, 2**31 + 7])
def test_float32_control_fails_the_limits(cell_name, seed, tmp_path):
    """The reference put in the program's place, computed in float32 where
    the configuration states doubles and exact decimals, has to come out as
    not correct."""
    import auron_tpu  # noqa: F401
    from benchmarks.harness import datagen
    cell = cells.load_cell(cell_name)
    cat = datagen.generate(str(tmp_path), cell.query.SCANS,
                           cell.config["rehearse_rows"],
                           cell.config["data_seed"], seed)
    params = cell.traffic["param_sets"][0]
    want = cell.query.reference(cat.read, params)
    control = cell.query.reference(cat.read, params, np.float32)
    verdict = compare.judge(compare.compare_tables(control, want),
                            cell.query.LIMITS)
    assert verdict["ok"] is False
    assert compare.judge(compare.compare_tables(want, want),
                         cell.query.LIMITS)["ok"] is True
    assert pc.equal(control.column(0), want.column(0)).to_pylist()[0]

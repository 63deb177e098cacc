"""`correct` against the faults that only a mesh can have, at
`--rehearse-cpu` scale on four virtual devices: planted in the program,
under the timed path of every cell that runs on more than one chip, each
has to read `correct` false.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python -m pytest benchmarks/selfcheck

The flag is for the whole directory: `test_correct.py` takes every cell of
BENCHMARK.json as a case, the four-chip cells among them.
"""

import json
import os

import pytest

from benchmarks import run
from benchmarks.harness import cells

with open(os.path.join(cells.REPO_DIR, "BENCHMARK.json")) as f:
    MESH_CELLS = [w["name"] for w in json.load(f)["workloads"]
                  if w["chips"] > 1]


@pytest.fixture
def stage():
    """The stage driver's module, its caches emptied before and after: a
    fault planted in a traced program must not outlive its test."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs four devices: run under XLA_FLAGS="
                    "--xla_force_host_platform_device_count=4")
    from auron_tpu.parallel import stage as S

    def empty():
        S._PROGRAM_CACHE.clear()
        S.clear_source_caches()
    empty()
    yield S
    empty()


def drive(cell_name):
    return run.run_cell(cells.load_cell(cell_name), seed=5, seconds=0.2,
                        traced=False, rehearse_cpu=True)


@pytest.mark.parametrize("cell_name", MESH_CELLS)
def test_a_sound_run_on_the_mesh_is_correct(stage, cell_name):
    result = drive(cell_name)
    assert result["correct"] is True and result["failed"] == 0
    assert result["device"]["count"] >= 4


@pytest.mark.parametrize("cell_name", MESH_CELLS)
def test_one_chips_share_of_the_fact_table_left_out(stage, cell_name,
                                                    monkeypatch):
    """Device 2's rows of the largest source arrive dead: the program
    answers over three quarters of store_sales."""
    cell = cells.load_cell(cell_name)
    most = max(cell.config["rehearse_rows"][t] for t in cell.query.SCANS)
    shard_table = stage._shard_table
    left_out = []

    def faulty(table, mesh, axis):
        schema, cols, live, cap = shard_table(table, mesh, axis)
        if table.num_rows == most:
            left_out.append(int(live[2 * cap:3 * cap].sum()))
            live = live.at[2 * cap:3 * cap].set(False)
        return schema, cols, live, cap

    monkeypatch.setattr(stage, "_shard_table", faulty)
    result = drive(cell_name)
    assert left_out and left_out[0] == -(-most // cell.chips)
    assert result["correct"] is False


@pytest.mark.parametrize("cell_name", MESH_CELLS)
@pytest.mark.parametrize("build_side", ["customer_demographics", "item"])
def test_a_build_side_gathered_from_three_chips_of_four(
        stage, cell_name, build_side, monkeypatch):
    """The all_gather of one build side loses device 3's rows: the joins
    on the other three chips' rows still match, the rest of the fact rows
    drop out of an inner join."""
    from auron_tpu.columnar.batch import bucket_capacity
    cell = cells.load_cell(cell_name)
    cap = bucket_capacity(
        -(-cell.config["rehearse_rows"][build_side] // cell.chips))
    gather = stage.broadcast_all_gather
    lost = []

    def faulty(arrays, valid, axis):
        outs, gathered = gather(arrays, valid, axis)
        # query 7 projects its filtered build sides to the key alone (a
        # column and its validity); item goes whole, the id's string too
        whole = len(arrays) > 2
        if valid.shape[0] == cap and whole == (build_side == "item"):
            lost.append(build_side)
            gathered = gathered.reshape(cell.chips, -1).at[3].set(
                False).reshape(-1)
        return outs, gathered

    monkeypatch.setattr(stage, "broadcast_all_gather", faulty)
    result = drive(cell_name)
    assert lost
    assert result["correct"] is False

"""TPC-DS integration tests: the full corpus through the differential
QueryRunner (the in-CI equivalent of the reference's tpcds.yml per-query
matrix).  Single-device runs at sf>=0.1 with the perf gate armed (warm
native must stay within 10x the numpy oracle); the mesh parametrization
stays at tiny scale so the shard_map compiles dominate less."""

import os

import pytest

from auron_tpu.it.datagen import generate
from auron_tpu.it.queries import names
from auron_tpu.it.runner import QueryRunner

SF = float(os.environ.get("AURON_IT_SF", "0.1"))


@pytest.fixture(scope="module")
def catalog(tmp_path_factory):
    return generate(str(tmp_path_factory.mktemp("tpcds")), sf=SF,
                    fact_chunks=4)


@pytest.fixture(scope="module")
def small_catalog(tmp_path_factory):
    return generate(str(tmp_path_factory.mktemp("tpcds_small")), sf=0.002,
                    fact_chunks=3)


@pytest.fixture(scope="module")
def runner(catalog, tmp_path_factory):
    # round 4: the stage path (default on) + device-resident source
    # caching killed the per-execute fixed cost the old 0.8s floor and
    # the three SMJ-chain waivers excused (corpus median warm/oracle
    # fell 1.65x -> 0.25x) — the gate now binds at 3x the ACTUAL oracle
    # for effectively the whole corpus, with an empty waiver list
    r = QueryRunner(catalog=catalog, perf_factor=3.0, perf_floor_s=0.2,
                    perf_waivers={})
    yield r
    # per-query native/oracle/warm seconds of this (CPU) run, kept with
    # pytest's own temporary files: a test run changes no tracked file
    out = tmp_path_factory.mktemp("it_perf") / "IT_PERF.json"
    out.write_text(r.to_json() + "\n")


# tier-1 keeps a representative subset of the corpus (every operator
# family: scans+pushdown, BHJ/SMJ/SHJ, two-phase/rollup aggs, window,
# expand, union, generate) under the 870s gate budget; the remaining
# queries run with the same fixtures under -m slow (nightly / full
# sweeps).  Every query here was red before the jax shard_map compat
# gate landed, so the split only widens coverage vs the seed.
#
# PR 5 budget re-measure (2026-08-05): tier-1 clocked 971s against the
# 870s timeout on a slow-drifted box (PR 4 measured 848s on a fast one;
# this machine drifts ±30%), so the slowest stragglers — each >=9s
# serial, families still covered by the remaining subset and by the
# nightly -m slow sweep — moved out of the gate.  Measured serial costs:
# q67r 20.2s, q39v 14.7s, q98 14.1s, q25m 13.8s, q76u 13.6s, q80s
# 13.4s, q56s 12.3s, q20c 12.1s, q68s 11.9s, q22r 10.9s, q43 10.3s,
# q79s 10.1s, q62w 9.1s (mesh variants of q80s/q56s/q62w/q39v add
# another ~48s).  Post-split tier-1: 604-26=578ish tests in ~700s.
# PR 12 budget re-measure (2026-08-05): tier-1 clocked 845s/870 on
# this box with the durable-shuffle additions (the rss kill-9 resume
# stress replaced the PR 11 fleet stress in tier-1 at ~same cost, the
# fast durable suite added ~15s), so five more stragglers move out —
# measured serial costs: q23c 10.9s, q27r 8.3s, q24s 7.9s, q74y 5.8s,
# q53m 5.8s (~39s) — plus the op-device chaos sweep (test_chaos.py,
# 13.9s).  q36r (8.0s) deliberately STAYS: it is the remaining
# in-tier rollup/sort query test_some_queries_ride_the_mesh pins.
# Post-split tier-1: 769 tests in ~725s on this box.
# PR 16 budget re-measure (2026-08-06): the wirecheck additions plus
# a slower box (the PR 15 corpus alone clocked 804s here) pushed
# tier-1 to 839s/870, so the kill-9/overload stresses and the q42
# AQE-equivalence variant moved to -m slow, and the SINGLE-DEVICE
# q36r (10.4s) moves out here — its mesh variant stays in tier-1
# because the rollup pin in test_some_queries_ride_the_mesh rides
# the mesh run, not this one.
_TIER1_STRAGGLERS = {
    "q67r", "q39v", "q98", "q25m", "q76u", "q80s", "q56s", "q20c",
    "q68s", "q22r", "q43", "q79s", "q62w",
    "q23c", "q27r", "q24s", "q74y", "q53m",
    # PR 18 tier-1 re-split (8.4s each; serial-only variants whose
    # operator families ride other tier-1 queries — nightly covers them)
    "q86r", "q14c",
}
_TIER1_QUERIES = (set(names()[::4]) | {
    "q03", "q07", "q42", "q55", "q13a", "q26a", "q48a", "q19", "q65w",
    "q71u", "q27r", "q93s", "q76u", "q22r", "q33b", "q60b", "q36r",
    "q62w", "q39v", "q56s", "q80s", "q01", "q16a", "q68s", "q98",
}) - _TIER1_STRAGGLERS


# PR 18 tier-1 re-split: queries whose MESH variant stays in tier-1
# (MESH_QUERIES below) drop their serial twin from the fast box —
# the serial path still runs them nightly, and serial q01/q93s/q55/...
# keep the single-device corpus exercised every push (~55s back)
_TIER1_SERIAL = _TIER1_QUERIES - {
    "q36r", "q03", "q42", "q19", "q71u", "q07", "q33b", "q60b"}


@pytest.mark.parametrize(
    "query",
    [q if q in _TIER1_SERIAL else
     pytest.param(q, marks=pytest.mark.slow) for q in names()])
def test_tpcds_query(runner, query):
    r = runner.run(query)
    assert r.error is None, f"{query}: {r.error}"
    assert r.perf_error is None, f"{query}: {r.perf_error}"
    assert r.all_native, f"{query} left foreign sections in the plan"
    assert r.rows > 0, f"{query} returned no rows"


@pytest.fixture(scope="module")
def mesh_runner(small_catalog):
    from auron_tpu.parallel.mesh import data_mesh
    return QueryRunner(catalog=small_catalog, mesh=data_mesh(8))


# representative mesh subset: the SPMD-compilable shapes (BHJ/agg/
# filter/project pipelines) plus fallback exemplars for every operator
# family the stage compiler rejects (smj, window, union, expand) — the
# full corpus already runs single-device above; re-running all 42 on the
# mesh only re-compiles the same fallback kernels at a second scale
MESH_QUERIES = ["q03", "q07", "q42", "q55", "q13a", "q26a", "q48a",
                "q19", "q65w", "q71u", "q27r", "q93s", "q76u", "q22r",
                "q33b", "q60b", "q36r",
                # round-3 families: ship-lag histograms (CaseWhen-bucket
                # aggs), stddev aggs, three-channel union, rollup-over-
                # union capstone
                "q62w", "q39v", "q56s", "q80s"]


@pytest.mark.parametrize(
    "query",
    [q if q not in _TIER1_STRAGGLERS else
     pytest.param(q, marks=pytest.mark.slow) for q in MESH_QUERIES])
def test_tpcds_query_multi_device(mesh_runner, query):
    """Corpus queries offered to the SPMD stage compiler over the
    8-device mesh: SPMD-compilable plans run as one shard_map program
    (collectives for the exchanges), the rest transparently fall back to
    the serial path — correctness holds either way."""
    r = mesh_runner.run(query)
    assert r.error is None, f"{query}: {r.error}"
    assert r.rows > 0, f"{query} returned no rows"


def test_some_queries_ride_the_mesh(mesh_runner):
    """The SPMD path must actually engage for part of the corpus (guards
    against the fallback silently swallowing everything) — including,
    since round 3, window- and sort/rollup-bearing queries (VERDICT #5)."""
    ran = {r.name for r in mesh_runner.results if r.spmd}
    assert len(ran) >= 2, \
        f"expected >=2 SPMD-executed corpus queries, got {sorted(ran)}"
    assert "q65w" in ran, "window-bearing q65w fell back to serial"
    assert {"q22r", "q27r", "q36r"} & ran, \
        f"no rollup/sort-bearing query rode the mesh: {sorted(ran)}"
    assert "q93s" in ran, "SMJ-bearing q93s fell back to serial"


def test_plan_stability(small_catalog, tmp_path, monkeypatch):
    """Same plan converted twice renders identically (golden round-trip)."""
    from auron_tpu.it import stability
    from auron_tpu import config
    from auron_tpu.frontend.session import AuronSession
    from auron_tpu.it.oracle import PyArrowEngine
    from auron_tpu.it.queries import build

    golden = str(tmp_path / "goldens")
    # a missing golden is a hard failure, not a silent auto-create
    monkeypatch.delenv("AURON_REGEN_GOLDEN", raising=False)
    session = AuronSession(foreign_engine=PyArrowEngine())
    res = session.execute(build("q03", small_catalog))
    text = stability.render_plan(res.converted, res.ctx)
    assert stability.check_stability("q03", text, golden) is not None
    monkeypatch.setenv("AURON_REGEN_GOLDEN", "1")
    assert stability.check_stability("q03", text, golden) is None
    monkeypatch.delenv("AURON_REGEN_GOLDEN")
    for attempt in range(2):
        session = AuronSession(foreign_engine=PyArrowEngine())
        res = session.execute(build("q03", small_catalog))
        text = stability.render_plan(res.converted, res.ctx)
        err = stability.check_stability("q03", text, golden)
        assert err is None, err
    # a conversion regression (agg falling back) must be caught
    with config.conf.scoped({"auron.enable.agg": False}):
        session = AuronSession(foreign_engine=PyArrowEngine())
        res = session.execute(build("q03", small_catalog))
        text2 = stability.render_plan(res.converted, res.ctx)
    assert text2 != text
    assert stability.check_stability("q03", text2, golden) is not None


def test_runner_exclusion_list(small_catalog):
    """Excluded queries are skipped with a documented reason (the
    reference's per-suite .exclude(...) lists)."""
    from auron_tpu.it.runner import QueryRunner

    r = QueryRunner(catalog=small_catalog,
                    exclusions={"q03": "known divergence: demo"})
    qr = r.run("q03")
    assert qr.ok and qr.skipped == "known divergence: demo"
    assert "SKIP" in r.report()

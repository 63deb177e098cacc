"""The host's seconds of a warm execute under names (PR 37): leaf spans
inside the serial tail's `task.execute` and after `spmd.fetch`, each around
one step's own work on a batch it already holds — never around a `yield` or
a pull of the child's iterator, so they cannot mis-nest in the pull-based
engine — and the count of the execute's blocking fetches, `host_syncs`.
Query 7 at the benchmark's `rehearse_rows`, through the stage path."""

import pytest

from auron_tpu import config
from auron_tpu.frontend.session import AuronSession
from auron_tpu.it.oracle import PyArrowEngine
from auron_tpu.runtime import tracing
from benchmarks.harness import cells, datagen
from benchmarks.queries import q07

# span -> (the span it lies under, the args it carries)
TAIL_LEAVES = {
    "task.plan": ("task.execute", {"operators"}),
    "ffi.to_device": ("task.execute", {"rows", "bytes", "cached"}),
    "sort.run": ("task.execute",
                 {"rows", "capacity", "keys", "batches_in"}),
    "project.eval": ("task.execute", {"rows", "exprs"}),
    "limit.cut": ("task.execute", {"rows_in", "rows_out"}),
    "task.to_host": ("task.execute", {"rows", "bytes", "blocked"}),
}
SORT_STEPS = {
    "sort.concat": ("sort.run", {"rows"}),
    "sort.keys": ("sort.run", {"rows", "key_columns"}),
    "sort.order": ("sort.run", {"rows"}),
    "sort.take": ("sort.run", {"rows"}),
    "sort.cut": ("sort.run", {"rows_in", "rows_out"}),
    "sort.rechunk": ("sort.run", {"rows"}),
}
NEW_SPANS = {"spmd.to_arrow": ("spmd.gather",
                               {"rows", "slots", "columns", "bytes"}),
             **TAIL_LEAVES, **SORT_STEPS}
# an execute's blocking fetches: `spmd.wait`, `spmd.fetch`, and the tail's
# one, `task.to_host` (the sort's and the projection's batches carry host
# counts, so no `num_rows` of theirs is a fetch)
HOST_SYNCS, TAIL_SYNCS = 3, 1


@pytest.fixture(scope="module")
def plan(tmp_path_factory):
    cell = cells.load_cell("tpcds-sf1.q07")
    cat = datagen.generate(
        str(tmp_path_factory.mktemp("tail-tracing")), q07.SCANS,
        cell.config["rehearse_rows"], cell.config["data_seed"], 7)
    return q07.build_plan(cat, cell.traffic["param_sets"][0])


@pytest.fixture(scope="module")
def executes(plan):
    """Two traced executes in a row of one warm session."""
    session = AuronSession(foreign_engine=PyArrowEngine())
    session.execute(plan)
    with config.conf.scoped({"auron.trace.enable": True}):
        return [session.execute(plan) for _ in range(2)]


@pytest.fixture(scope="module")
def spans(executes):
    return [s for s in executes[-1].trace.snapshot() if s.dur_ns >= 0]


@pytest.fixture(scope="module")
def sites_off(plan):
    """name -> what `tracing.span` returned at each site of an execute
    with tracing off."""
    session = AuronSession(foreign_engine=PyArrowEngine())
    seen = {}
    real = tracing.span

    def spy(name, *a, **kw):
        got = real(name, *a, **kw)
        seen.setdefault(name, []).append(got)
        return got

    tracing.span = spy
    try:
        res = session.execute(plan)
    finally:
        tracing.span = real
    assert res.spmd and res.trace is None
    return seen


def _ancestors(span, by_id):
    while span.parent:
        span = by_id[span.parent]
        yield span.name


@pytest.mark.parametrize("name", sorted(NEW_SPANS))
def test_span_with_its_args_under_its_parent(spans, name):
    parent, args = NEW_SPANS[name]
    by_id = {s.id: s for s in spans}
    found = [s for s in spans if s.name == name]
    assert found, name
    for s in found:
        assert args <= set(s.args), (name, s.args)
        assert by_id[s.parent].name == parent
        top = "spmd.gather" if name == "spmd.to_arrow" else "task.execute"
        assert top in _ancestors(s, by_id)
        # inside its parent on the parent's thread
        p = by_id[s.parent]
        assert p.tid == s.tid and p.t0_ns <= s.t0_ns and \
            s.t0_ns + s.dur_ns <= p.t0_ns + p.dur_ns


@pytest.mark.parametrize("group", ["tail", "sort"])
def test_no_two_leaves_overlap(spans, group):
    """The rule that keeps spans from mis-nesting in a pull-based engine:
    one closes before the next opens."""
    names = TAIL_LEAVES if group == "tail" else SORT_STEPS
    leaves = sorted((s for s in spans if s.name in names),
                    key=lambda s: s.t0_ns)
    assert len({s.tid for s in leaves}) == 1
    for a, b in zip(leaves, leaves[1:]):
        assert a.t0_ns + a.dur_ns <= b.t0_ns, (a.name, b.name)


def test_the_tails_plan_and_what_crossed(spans):
    [plan_] = [s for s in spans if s.name == "task.plan"]
    assert plan_.args["operators"] == 3     # projection <- sort <- reader
    [up] = [s for s in spans if s.name == "ffi.to_device"]
    [table] = [s for s in spans if s.name == "spmd.to_arrow"]
    [down] = [s for s in spans if s.name == "task.to_host"]
    [run] = [s for s in spans if s.name == "sort.run"]
    # the gathered rows go up again, whole, and come down cut
    assert up.args["rows"] == table.args["rows"] == run.args["rows"] > 0
    assert up.args["bytes"] == table.args["bytes"] > 0
    assert table.args["slots"] >= table.args["rows"]
    assert down.args["rows"] == min(100, up.args["rows"])
    [cut] = [s for s in spans if s.name == "limit.cut"]
    assert cut.args["rows_out"] == down.args["rows"]


def test_children_cover_the_tails_task(spans):
    [task] = [s for s in spans if s.name == "task.execute"]
    covered = sum(s.dur_ns for s in spans if s.parent == task.id)
    assert covered >= 0.8 * task.dur_ns, (covered, task.dur_ns)
    [gather] = [s for s in spans if s.name == "spmd.gather"]
    covered = sum(s.dur_ns for s in spans if s.parent == gather.id)
    assert covered >= 0.8 * gather.dur_ns


@pytest.mark.parametrize("nth", [0, 1])
def test_host_syncs_pinned(executes, nth):
    res = executes[nth]
    rec = tracing.find_query(res.query_id)
    assert rec.metric_totals["host_syncs"] == HOST_SYNCS
    [task] = [s for s in res.trace.snapshot() if s.name == "task.execute"]
    assert task.args["syncs"] == TAIL_SYNCS


@pytest.mark.parametrize("name", sorted(NEW_SPANS))
def test_tracing_off_every_new_site_is_the_shared_noop(sites_off, name):
    got = sites_off.get(name)
    assert got, f"{name}: the site was not reached"
    noop = tracing.span("scan.decode", cat="scan")
    assert not noop.armed
    assert all(g is noop for g in got)


def test_host_syncs_counted_with_tracing_off(plan):
    session = AuronSession(foreign_engine=PyArrowEngine())
    res = session.execute(plan)
    assert res.trace is None
    totals = tracing.find_query(res.query_id).metric_totals
    assert totals["host_syncs"] == HOST_SYNCS

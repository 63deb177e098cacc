"""The stage driver's two byte-budgeted source caches (`_SCAN_TABLES`: the
Arrow a scan leaf read; `_DEVICE_SHARDS`: its padded shards on the device)
never evict what the execute in flight reads (PR 33): the budget evicts,
least recently used first, among the other entries only.  Where a query
fits the budgets, as every SF1 cell does, the sequence of stores, hits and
evictions is the one the rule before it ("keep at least one entry") gave."""

import collections
import os

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq
import pytest

from auron_tpu.config import conf
from auron_tpu.frontend.converters import BroadcastJob
from auron_tpu.ir import expr as E
from auron_tpu.ir import plan as P
from auron_tpu.ir.expr import col, lit
from auron_tpu.ir.plan import JoinOn
from auron_tpu.ir.schema import from_arrow_schema
from auron_tpu.parallel import stage as S
from auron_tpu.parallel.mesh import data_mesh
from auron_tpu.runtime import tracing

MB = 1 << 20
FACT_ROWS = 70_000          # two int64 columns: 1,120,000 B, over 1 MB
KEYS = 64
SCAN_MB = "auron.spmd.scan.cache.mb"
SHARD_MB = "auron.spmd.source.cache.mb"
# the option that bounds each cache, the cache, and the span that reports it
CACHES = {"scan": (SCAN_MB, S._SCAN_TABLES, "spmd.ingest"),
          "shard": (SHARD_MB, S._DEVICE_SHARDS, "spmd.shard")}


class _Ctx:
    def __init__(self):
        self.exchanges = {}
        self.broadcasts = {}


@pytest.fixture(autouse=True)
def cold_caches():
    S.clear_source_caches()
    yield
    S.clear_source_caches()


def fact_table(stamp=0):
    k = np.arange(FACT_ROWS, dtype=np.int64)
    return pa.table({"key": k % KEYS, "v": k + stamp})


def dim_table(name, keys=None):
    keys = np.arange(KEYS, dtype=np.int64) if keys is None else keys
    return pa.table({f"{name}key": keys,
                     f"{name}val": np.arange(len(keys), dtype=np.int64)})


def write(table, path):
    pq.write_table(table, str(path))
    return str(path)


def scan_of(path):
    return P.ParquetScan(
        schema=from_arrow_schema(pq.read_schema(path)),
        file_groups=(P.FileGroup(paths=(path,)),))


def join_plan(d, dims=("a",), dim_keys=None):
    """A few rows of a fact file joined to each of `dims`, every leaf a
    parquet scan of a file under `d`: (plan, ctx, fact path, leaves by
    name)."""
    os.makedirs(d, exist_ok=True)
    fact_path = write(fact_table(), os.path.join(d, "fact.parquet"))
    leaves = {"fact": scan_of(fact_path)}
    ctx = _Ctx()
    node = P.Filter(child=leaves["fact"], predicates=(
        E.BinaryExpr(op="<", left=col("v"), right=lit(200)),))
    for name in dims:
        leaves[name] = scan_of(write(dim_table(name, dim_keys),
                                     os.path.join(d, f"{name}.parquet")))
        ctx.broadcasts["bc" + name] = BroadcastJob(
            rid="bc" + name, child=leaves[name], schema=None)
        node = P.BroadcastJoin(
            left=node, right=P.IpcReader(schema=None,
                                         resource_id="bc" + name),
            on=JoinOn(left_keys=(col("key"),),
                      right_keys=(col(f"{name}key"),)),
            join_type="inner", broadcast_side="right")
    return node, ctx, fact_path, leaves


def run(plan, ctx, mesh=None, stats=None):
    """One execute under an armed recorder: (table, its spans)."""
    rec = tracing.TraceRecorder("q", max_events=10_000)
    with tracing.trace_scope(recorder=rec, query_id="q"):
        with tracing.span("query", cat="query", query_id="q"):
            table = S.execute_plan_spmd(plan, ctx, mesh or data_mesh(1), {},
                                        stats=stats)
    return table, [s for s in rec.snapshot() if s.dur_ns >= 0]


def named(spans, name):
    return [s for s in spans if s.name == name]


def replace(path, stamp):
    """The file rewritten with other rows of the same shape, under a later
    modification time: a writer has replaced it."""
    st = os.stat(path)
    pq.write_table(fact_table(stamp), path)
    later = st.st_mtime_ns + 1_000_000 * (stamp + 1)
    os.utime(path, ns=(later, later))


# -- (i) what the query in flight reads is the floor ----------------------

def test_a_leaf_over_the_budget_stays_beside_its_plans_leaves(tmp_path):
    """One store_sales over `auron.spmd.scan.cache.mb` by itself, and
    its shards over `auron.spmd.source.cache.mb`: SF10's shape.  The first
    execute keeps all of what it read in both caches, the second runs no
    scan task, no `shard.pad` and no `shard.put`."""
    plan, ctx, _path, leaves = join_plan(str(tmp_path), dims=("a", "b"))
    assert fact_table().nbytes > MB
    with conf.scoped({SCAN_MB: 1, SHARD_MB: 1}):
        first = {}
        table, spans = run(plan, ctx, stats=first)
        assert table.num_rows == 200
        # Arrow's bytes of the three tables as the scans read them
        arrow_bytes = sum(t.nbytes for t in S._SCAN_TABLES.values())
        assert arrow_bytes > fact_table().nbytes
        [ingest] = named(spans, "spmd.ingest")
        assert {k: ingest.args[k] for k in
                ("scans", "cached", "tasks") + S.CACHE_STATE} == {
            "scans": 3, "cached": 0, "tasks": 3, "evicted": 0,
            "held_bytes": arrow_bytes,
            "over_budget_bytes": arrow_bytes - MB}
        [shard] = named(spans, "spmd.shard")
        device_bytes = shard.args["held_bytes"]
        assert device_bytes > fact_table().nbytes
        # what was put is what is held: every source crossed once
        assert {k: shard.args[k] for k in S.SHARD_COUNTS} == {
            "cached": 0, "placed": 3, "shard_put_bytes": device_bytes,
            "evicted": 0, "held_bytes": device_bytes,
            "over_budget_bytes": device_bytes - MB}
        assert len(named(spans, "shard.pad")) == 3
        # every leaf is there, the heavy one among them
        for node in leaves.values():
            assert S._SCAN_TABLES.get(node, S._scan_files_fp(node)) \
                is not None
        assert len(S._DEVICE_SHARDS.values()) == 3

        again = {}
        table2, spans2 = run(plan, ctx, stats=again)
        assert table2.equals(table)
        [ingest2] = named(spans2, "spmd.ingest")
        assert {k: ingest2.args[k] for k in S.INGEST_COUNTS} == {
            "scans": 3, "cached": 3, "tasks": 0, "batches": 0, "rows": 0,
            "bytes": 0, "device_batches": 0}
        for name in ("task.execute", "scan.decode", "shard.pad",
                     "shard.put"):
            assert named(spans2, name) == [], name
        [shard2] = named(spans2, "spmd.shard")
        assert {k: shard2.args[k] for k in S.SHARD_COUNTS} == {
            "cached": 3, "placed": 0, "shard_put_bytes": 0, "evicted": 0,
            "held_bytes": device_bytes,
            "over_budget_bytes": device_bytes - MB}
        # and as query totals, where `scan_rows` goes
        assert S.stage_totals(again) == {
            "scan_rows": 0, "scan_batches": 0, "scan_device_batches": 0,
            "scan_cached": 3, "shards_cached": 3, "shard_put_bytes": 0,
            "source_evictions": 0,
            "source_over_budget_bytes":
                arrow_bytes - MB + device_bytes - MB,
            "join_probes": 2, "join_probes_direct": 2,
            "agg_inputs": 0, "agg_inputs_compact": 0,
            "agg_inputs_below_cap": 0,
            # the two joins are a chain over the fact source, whose few
            # live rows run the second join at a rung
            "join_chains": 1, "join_chains_compact": 1,
            # the plan holds no aggregate
            "segment_bounds": 0, "segment_reductions": 0}
        assert S.stage_totals(first)["scan_cached"] == 0
        assert S.stage_totals(first)["shard_put_bytes"] == device_bytes
        assert S.stage_totals(first)["scan_rows"] == FACT_ROWS + 2 * KEYS


def test_without_the_floor_the_same_query_reads_everything_again(
        tmp_path, monkeypatch):
    """The control of the test above: hand the caches no keys (the rule
    before PR 33: the newest entry alone is kept past the budget) and the
    heavy leaf's store evicts the plan's other leaves, theirs evict it,
    and every execute runs scan tasks and pads and puts again."""
    real = S._ByteBudgetLRU._store
    monkeypatch.setattr(
        S._ByteBudgetLRU, "_store",
        lambda self, key, value, nbytes, reads=(): real(
            self, key, value, nbytes))
    plan, ctx, _path, _leaves = join_plan(str(tmp_path), dims=("a", "b"))
    with conf.scoped({SCAN_MB: 1, SHARD_MB: 1}):
        run(plan, ctx)
        _table, spans = run(plan, ctx)
    [ingest] = named(spans, "spmd.ingest")
    assert ingest.args["tasks"] > 0 and ingest.args["evicted"] > 0
    assert named(spans, "shard.pad") and named(spans, "shard.put")


# -- (ii) the budget still evicts, least recently used first --------------

@pytest.mark.parametrize("which", sorted(CACHES))
def test_the_budget_evicts_other_plans_least_recently_used_first(
        which, tmp_path):
    option, cache, span_name = CACHES[which]
    plans = {name: join_plan(str(tmp_path / name))[:2] for name in "ABC"}

    def step(name):
        """Run plan `name`; returns (keys before, least recently used
        first, with their bytes; keys after; the span's counts)."""
        before = [(k, b) for k, (_v, b) in cache._entries.items()]
        _t, spans = run(*plans[name])
        [span] = named(spans, span_name)
        return before, list(cache._entries), span.args

    with conf.scoped({option: 1}):
        # each plan's leaves are over the budget by themselves
        _b, own_a, args = step("A")
        assert len(own_a) == 2 and args["evicted"] == 0
        one_plan = cache.held_bytes()
        assert args["over_budget_bytes"] == one_plan - MB > 0
    budget = -(-2 * one_plan // MB)         # two plans fit, three do not
    assert 3 * one_plan > budget * MB >= 2 * one_plan
    with conf.scoped({option: budget}):
        step("B")
        before, after, args = step("C")
        # A's entries are the least recently used: they go first, and
        # only as many as the budget asks for
        gone = [k for k, _b in before if k not in after]
        assert gone and gone == [k for k, _b in before][:len(gone)]
        assert set(gone) <= set(own_a)
        assert args["evicted"] == len(gone)
        assert cache.held_bytes() <= budget * MB
        assert args["over_budget_bytes"] == 0
        last = dict(before)[gone[-1]]
        assert cache.held_bytes() + last > budget * MB
        # B is touched, so C is the least recently used when A comes back
        _b, after, args = step("B")
        own_b = after[-2:]
        assert args["evicted"] == 0 and args["cached"] == 2
        before, after, args = step("A")
        gone = [k for k, _b in before if k not in after]
        assert gone and not set(gone) & set(own_b)
        assert gone == [k for k, _b in before][:len(gone)]


# -- (iii) the SF1 shape is the parent's ----------------------------------

# `_SCAN_TABLES` after each execute, least recently used first: `f<k>` the
# fact file's k-th replacement, `a` the dimension; budget 4 MB, a fact copy
# 1,120,000 B: three copies and the dimension fit, four do not.  Written
# out from the rule before PR 33 (evict from the front while over the
# budget), and read off the parent commit's tree by the same steps.
PARENTS_SEQUENCE = [
    "a f0",
    "f0 a f1",
    "f0 f1 a f2",
    "f1 f2 a f3",
    "f2 f3 a f4",
    "f3 f4 a f5",
    "f4 f5 a f6",
    "f5 f6 a f7",
    "f6 f7 a f8",
    "f7 f8 a f9",
    "f8 f9 a f10",
]


def test_a_replaced_file_leaves_the_copies_the_parents_rule_left(tmp_path):
    """A file replaced ten times under a budget of three copies: the
    working set (the dimension and one copy) is inside the budget and the
    most recently used, so the floor never binds and the evictions are
    the parent's, copy for copy.  A copy's shards go when its table does,
    as before."""
    plan, ctx, path, leaves = join_plan(str(tmp_path))
    names = {}
    held, evicted, shards = [], [], []
    with conf.scoped({SCAN_MB: 4}):
        for k in range(11):
            if k:
                replace(path, k)
            names[(leaves["fact"], S._scan_files_fp(leaves["fact"]))] = \
                f"f{k}"
            names[(leaves["a"], S._scan_files_fp(leaves["a"]))] = "a"
            stats = {}
            table, _spans = run(plan, ctx, stats=stats)
            assert table.column("v").to_pylist() == list(range(k, 200))
            held.append(" ".join(names[key]
                                 for key in S._SCAN_TABLES._entries))
            evicted.append(stats["ingest"]["evicted"])
            shards.append(len(S._DEVICE_SHARDS.values()))
            assert stats["ingest"]["over_budget_bytes"] == 0
            assert stats["shard"]["evicted"] == 0
            assert stats["shard"]["over_budget_bytes"] == 0
    assert held == PARENTS_SEQUENCE
    assert evicted == [0, 0, 0] + [1] * 8
    assert shards == [2, 3, 4] + [4] * 8


def _parents_rule(entries, used, budget, key, nbytes):
    """`_ByteBudgetLRU._store` as it was before PR 33."""
    used -= entries.pop(key, 0)
    entries[key] = nbytes
    used += nbytes
    while used > budget and len(entries) > 1:
        _k, b = entries.popitem(last=False)
        used -= b
    return used


class _Fixed(S._ByteBudgetLRU):
    def __init__(self, budget):
        super().__init__()
        self.budget = budget

    def _budget(self):
        return self.budget


@pytest.mark.parametrize("seed", range(6))
def test_a_working_set_inside_the_budget_is_evicted_as_before(seed):
    """Random executes (hits of what is cached, then stores of the rest,
    as `_materialize_scans` makes them) whose working sets fit the
    budget: entry for entry, in order, what the rule before left."""
    rng = np.random.default_rng(seed)
    budget = 120
    cache = _Fixed(budget)
    want = collections.OrderedDict()
    used = 0
    for _execute in range(200):
        reads = [(int(k), int(rng.integers(0, 3)))
                 for k in rng.choice(12, int(rng.integers(1, 5)),
                                     replace=False)]
        sizes = {key: 5 + 2 * key[0] for key in reads}
        assert sum(sizes.values()) <= budget
        misses = []
        for key in reads:
            if cache._lookup(key) is None:
                misses.append(key)
            else:
                want.move_to_end(key)
        for key in misses:
            cache._store(key, object(), sizes[key], set(reads))
            used = _parents_rule(want, used, budget, key, sizes[key])
        assert list(cache._entries) == list(want)
        assert cache.held_bytes() == used <= budget
        assert cache.over_budget_bytes() == 0


RULE_CASES = {
    # name: (budget, [(key, bytes, reads)], keys left, bytes over)
    "no keys handed in: the newest entry alone is the floor":
        (10, [("a", 6, ()), ("b", 6, ()), ("c", 30, ())], ["c"], 20),
    "what the attempt reads stays, over the budget":
        (10, [("a", 6, "a"), ("b", 30, "ab"), ("c", 6, "abc")],
         ["a", "b", "c"], 32),
    "the others go first, least recently used first":
        (20, [("x", 6, "x"), ("y", 6, "y"), ("z", 6, "z"),
              ("a", 6, "a"), ("b", 6, "ab")], ["z", "a", "b"], 0),
    "an entry read by the attempt is passed over, not the rest":
        (20, [("x", 6, "x"), ("y", 6, "y"), ("z", 6, "z"),
              ("b", 6, "xb")], ["x", "z", "b"], 0),
    "all the others gone, what is read stays":
        (10, [("x", 6, "x"), ("a", 8, "a"), ("b", 8, "ab")],
         ["a", "b"], 6),
    "a key stored again counts once":
        (10, [("a", 6, "a"), ("a", 8, "a"), ("b", 2, "ab")],
         ["a", "b"], 0),
}


@pytest.mark.parametrize("case", sorted(RULE_CASES))
def test_the_rule(case):
    budget, stores, left, over = RULE_CASES[case]
    cache = _Fixed(budget)
    for key, nbytes, reads in stores:
        cache._store(key, key.upper(), nbytes, set(reads))
    assert list(cache._entries) == left
    assert cache.values() == [k.upper() for k in left]
    assert cache.over_budget_bytes() == over
    assert cache.held_bytes() == sum(b for _v, b in cache._entries.values())


# -- (iv) budget 0 disables and clears ------------------------------------

@pytest.mark.parametrize("which", sorted(CACHES))
def test_budget_zero_disables_and_clears(which, tmp_path):
    option, cache, span_name = CACHES[which]
    plan, ctx, _path, _leaves = join_plan(str(tmp_path))
    table, _spans = run(plan, ctx)
    assert len(cache.values()) == 2 and cache.held_bytes() > 0
    with conf.scoped({option: 0}):
        for _ in range(2):
            stats = {}
            again, spans = run(plan, ctx, stats=stats)
            assert again.equals(table)
            [span] = named(spans, span_name)
            assert (span.args["cached"], span.args["evicted"],
                    span.args["held_bytes"],
                    span.args["over_budget_bytes"]) == (0, 0, 0, 0)
            assert cache.values() == [] and cache.held_bytes() == 0
            assert S.stage_totals(stats)["source_over_budget_bytes"] == 0
            if which == "scan":
                # nothing kept: read again, and its shards placed again
                assert span.args["tasks"] == 2
            assert len(named(spans, "shard.pad")) == 2
    # the budget back, it caches again
    run(plan, ctx)
    _t, spans = run(plan, ctx)
    assert named(spans, "shard.pad") == []
    assert len(cache.values()) == 2


# -- (v) a guard retry inside one execute ---------------------------------

def test_a_guard_retry_reads_nothing_again(tmp_path):
    """Duplicate build keys trip the join's guard and the execute retries
    at the match factor: the second attempt is served what the first one
    stored, leaf over the budget and all."""
    keys = np.array([1, 1, 2], dtype=np.int64)
    plan, ctx, _path, _leaves = join_plan(str(tmp_path), dim_keys=keys)
    S._MATCH_FACTOR_HINT.clear()
    stats = {}
    with conf.scoped({SCAN_MB: 1, SHARD_MB: 1}):
        table, spans = run(plan, ctx, stats=stats)
    assert len(S._MATCH_FACTOR_HINT) == 1
    S._MATCH_FACTOR_HINT.clear()
    # v < 200: four rows each of keys 1 (twice in the dimension) and 2
    assert table.num_rows == 2 * 4 + 4
    first, second = named(spans, "spmd.ingest")
    assert (first.args["cached"], first.args["tasks"]) == (0, 2)
    assert (second.args["cached"], second.args["tasks"],
            second.args["rows"], second.args["evicted"]) == (2, 0, 0, 0)
    assert [s.args["placed"] for s in named(spans, "spmd.shard")] == [2, 0]
    assert [s.args["cached"] for s in named(spans, "spmd.shard")] == [0, 2]
    assert len(named(spans, "task.execute")) == 2
    assert len(named(spans, "shard.pad")) == 2
    # over the attempts the counts add up and the caches' bytes are the
    # last attempt's
    assert stats["ingest"]["scans"] == 4 and stats["ingest"]["cached"] == 2
    assert stats["ingest"]["held_bytes"] == second.args["held_bytes"]
    assert stats["ingest"]["over_budget_bytes"] == \
        second.args["held_bytes"] - MB > 0
    assert stats["shard"] == {
        "cached": 2, "placed": 2,
        "shard_put_bytes": S._DEVICE_SHARDS.held_bytes(), "evicted": 0,
        "held_bytes": S._DEVICE_SHARDS.held_bytes(),
        "over_budget_bytes": S._DEVICE_SHARDS.held_bytes() - MB}


# -- (vi) two meshes -------------------------------------------------------

def test_two_meshes_neither_serve_nor_pin_each_others_shards(tmp_path):
    """Shards are placed for one mesh: another mesh's execute of the same
    tables is served none of them (it is served the Arrow), and its
    stores evict them like any other entry: the keys an attempt hands in
    are its own mesh's."""
    plan, ctx, _path, _leaves = join_plan(str(tmp_path))
    one, two = data_mesh(1), data_mesh(2)
    with conf.scoped({SHARD_MB: 1}):
        want, spans = run(plan, ctx, mesh=one)
        [shard] = named(spans, "spmd.shard")
        assert (shard.args["placed"], shard.args["evicted"]) == (2, 0)
        of_one = list(S._DEVICE_SHARDS._entries)

        got, spans = run(plan, ctx, mesh=two)
        assert sorted(got.column("v").to_pylist()) == \
            sorted(want.column("v").to_pylist())
        [ingest] = named(spans, "spmd.ingest")
        assert (ingest.args["cached"], ingest.args["tasks"]) == (2, 0)
        [shard] = named(spans, "spmd.shard")
        # not served, and over the budget: the first mesh's shards go
        assert (shard.args["cached"], shard.args["placed"],
                shard.args["evicted"]) == (0, 2, 2)
        of_two = list(S._DEVICE_SHARDS._entries)
        assert len(of_two) == 2 and not set(of_two) & set(of_one)
        assert shard.args["over_budget_bytes"] == \
            S._DEVICE_SHARDS.held_bytes() - MB > 0

        _t, spans = run(plan, ctx, mesh=one)
        [shard] = named(spans, "spmd.shard")
        assert (shard.args["cached"], shard.args["placed"],
                shard.args["evicted"]) == (0, 2, 2)
        assert list(S._DEVICE_SHARDS._entries) == of_one
    # inside the budget both meshes' shards stay, each served its own
    run(plan, ctx, mesh=two)
    for mesh in (one, two):
        _t, spans = run(plan, ctx, mesh=mesh)
        [shard] = named(spans, "spmd.shard")
        assert (shard.args["cached"], shard.args["placed"]) == (2, 0)
    assert len(S._DEVICE_SHARDS.values()) == 4

"""The one kernel each job has, against plain references: the composed
stable key sort against numpy's, the double-`searchsorted` probe against
numpy's, whole joins and sorts against row-at-a-time Python, and the
sort-based group-reduce against a dictionary group-by."""

from decimal import Decimal

import numpy as np
import pyarrow as pa
import pytest

import jax.numpy as jnp

from auron_tpu.columnar.batch import Batch
from auron_tpu.config import conf
from auron_tpu.ir.expr import SortExpr, col
from auron_tpu.ir.schema import DataType, Field, Schema, from_arrow_schema
from auron_tpu.memmgr.manager import reset_manager
from auron_tpu.ops.base import TaskContext
from auron_tpu.ops.basic import MemoryScanExec
from auron_tpu.ops.sort import SortExec
from auron_tpu.ops.sort_keys import lexsort_indices_live, stable_argsort

I64 = DataType.int64()


# -- key sort ----------------------------------------------------------------

@pytest.mark.parametrize("live", ["all", "half"])
@pytest.mark.parametrize("dtype", [np.uint32, np.uint64])
@pytest.mark.parametrize("n_words", [1, 2, 3])
def test_lexsort_indices_live_matches_np_lexsort(n_words, dtype, live):
    """Randomized capacities and word kinds (wide, duplicate-heavy for
    stability, one-bit rank words, descending flips); dead rows last in
    their own order."""
    rng = np.random.default_rng(42 + n_words)
    top = np.iinfo(dtype).max
    for trial in range(6):
        cap = int(rng.integers(2, 4000))
        words = []
        for _ in range(n_words):
            hi = [top, 5, 2][int(rng.integers(0, 3))]
            w = rng.integers(0, hi, cap, dtype=dtype, endpoint=True)
            words.append(~w if rng.random() < 0.3 else w)
        mask = np.ones(cap, bool) if live == "all" else rng.random(cap) < 0.5
        got = np.asarray(lexsort_indices_live(
            [jnp.asarray(w) for w in words], jnp.asarray(mask)))
        want = np.lexsort(tuple(reversed([~mask] + words)))
        np.testing.assert_array_equal(got, want, err_msg=f"trial {trial}")


@pytest.mark.parametrize("key", ["u64", "flags"])
def test_stable_argsort_matches_np_stable(key):
    rng = np.random.default_rng(7)
    keys = [rng.random(2000) < 0.5] if key == "flags" else \
        [rng.integers(0, top, 3000).astype(np.uint64)
         for top in (3, 1 << 20)]
    for k in keys:
        got = stable_argsort(jnp.asarray(k))
        assert got.dtype == jnp.int32
        np.testing.assert_array_equal(np.asarray(got),
                                      np.argsort(k, kind="stable"))


# -- join probe ----------------------------------------------------------------

def _probe_case(shape):
    """(sorted build hashes, probe hashes) of one shape."""
    rng = np.random.default_rng(9)
    if shape == "single-value":
        # every build row one hash value
        return (np.full(512, 0x1234, np.uint64),
                np.array([0x1234, 0x1235, 0], np.uint64))
    if shape == "power-of-two-span":
        # 2^m distinct hashes that differ in their low bits alone, every
        # member probed (the shape that lost a match in a kernel since
        # retired, PR 15's addendum)
        vals = np.concatenate([
            np.uint64(0x1234567800000000 + (m << 32)) +
            np.arange(1 << m, dtype=np.uint64) for m in range(1, 7)])
        return np.sort(vals), vals
    # duplicate-heavy values spread over the hash range, a quarter of the
    # build side under the null sentinel
    cap = 3000
    spread = np.uint64(0x0400000000000000)
    vals = rng.integers(0, 60, cap).astype(np.uint64) * spread
    vals[: cap // 4] = np.uint64(0xFFFFFFFFFFFFFFFF)
    return np.sort(vals), rng.integers(0, 64, 500).astype(np.uint64) * spread


@pytest.mark.parametrize("shape", ["randomized", "single-value",
                                   "power-of-two-span"])
def test_probe_ranges_matches_np_searchsorted(shape):
    from auron_tpu.ops.joins.kernel import probe_ranges
    sh, ph = _probe_case(shape)
    ones = jnp.ones(len(ph), bool)
    lo, counts = probe_ranges(jnp.asarray(sh), jnp.asarray(ph), ones, ones)
    want_lo = np.searchsorted(sh, ph, side="left")
    want = np.searchsorted(sh, ph, side="right") - want_lo
    np.testing.assert_array_equal(np.asarray(counts), want)
    np.testing.assert_array_equal(np.asarray(lo), want_lo)
    if shape == "power-of-two-span":
        assert want.tolist() == [1] * len(ph)
    if shape == "single-value":
        assert want.tolist() == [512, 0, 0]


def _scan(rows):
    t = pa.Table.from_pylist(rows)
    return MemoryScanExec(
        from_arrow_schema(t.schema),
        [Batch.from_arrow(b) for b in t.to_batches(max_chunksize=64)])


def _join_oracle(rows_l, rows_r, join_type):
    """Row-at-a-time equi-join on k = k2; a null key matches nothing."""
    out, matched_r = [], set()
    for left in rows_l:
        hits = [j for j, r in enumerate(rows_r)
                if left["k"] is not None and r["k2"] == left["k"]]
        matched_r.update(hits)
        if join_type == "left_semi":
            out.extend([left] if hits else [])
        elif join_type == "left_anti":
            out.extend([] if hits else [left])
        else:
            out.extend({**left, **rows_r[j]} for j in hits)
            if not hits and join_type in ("left", "full"):
                out.append({**left, "k2": None, "rv": None})
    if join_type == "full":
        out.extend({"k": None, "lv": None, **r}
                   for j, r in enumerate(rows_r) if j not in matched_r)
    return out


@pytest.mark.parametrize("join_type", ["inner", "left", "full",
                                       "left_semi", "left_anti"])
def test_hash_join_matches_a_row_at_a_time_join(join_type):
    """Duplicate keys on both sides, null keys, misses."""
    from auron_tpu.ir.plan import JoinOn
    from auron_tpu.ops.joins.exec import HashJoinExec
    rng = np.random.default_rng(13)
    rows_l = [{"k": (int(rng.integers(0, 40)) if rng.random() > 0.1
                     else None), "lv": i} for i in range(400)]
    rows_r = [{"k2": (int(rng.integers(0, 50)) if rng.random() > 0.1
                      else None), "rv": i} for i in range(300)]
    join = HashJoinExec(_scan(rows_l), _scan(rows_r),
                        JoinOn(left_keys=(col("k"),),
                               right_keys=(col("k2"),)), join_type)
    got = pa.Table.from_batches(
        [b.to_arrow() for b in join.execute_with_metrics(TaskContext())]
    ).to_pylist()

    def key(r):
        return tuple(sorted((k, str(v)) for k, v in r.items()))
    want = _join_oracle(rows_l, rows_r, join_type)
    assert len(got) == len(want) > 0
    assert sorted(map(key, got)) == sorted(map(key, want))


# -- sort operator ---------------------------------------------------------------

@pytest.fixture
def fresh_memmgr():
    reset_manager()
    yield
    conf.unset("auron.memory.spill.min.trigger.bytes")
    reset_manager()


def _sort_rows(rows, exprs, budget=None, chunk=200, limit=None):
    t = pa.Table.from_pylist(rows)
    if budget:
        conf.set("auron.memory.spill.min.trigger.bytes", 10_000)
        reset_manager(budget_bytes=budget)
    s = SortExec(
        MemoryScanExec(from_arrow_schema(t.schema),
                       [Batch.from_arrow(b)
                        for b in t.to_batches(max_chunksize=chunk)]),
        exprs, fetch_limit=limit)
    out = [b.to_arrow() for b in s.execute_with_metrics(TaskContext())]
    return pa.Table.from_batches(out).to_pylist(), \
        s.metrics.get("mem_spill_count")


def test_sort_exec_matches_python_sorted(fresh_memmgr):
    """Descending nulls-last integer key, then an ascending double; with
    and without a fetch limit."""
    rng = np.random.default_rng(31)
    rows = [{"k": int(rng.integers(-50, 50)) if rng.random() > 0.08
             else None,
             "f": float(rng.normal()), "i": i} for i in range(3000)]
    exprs = [SortExpr(child=col("k"), asc=False, nulls_first=False),
             SortExpr(child=col("f"), asc=True)]
    want = sorted(rows, key=lambda r: (r["k"] is None, -(r["k"] or 0),
                                       r["f"]))
    assert _sort_rows(rows, exprs)[0] == want
    assert _sort_rows(rows, exprs, limit=37)[0] == want[:37]


def test_sort_spill_merge_matches_python_sorted(fresh_memmgr):
    """Spilled sorted runs merge (ops/sort.py's host merger) to the order
    the in-memory sort gives: Python's stable sort of the rows."""
    rng = np.random.default_rng(33)
    rows = [{"k": int(v), "i": i}
            for i, v in enumerate(rng.integers(-10**6, 10**6, 6000))]
    exprs = [SortExpr(child=col("k"), asc=True)]
    want = sorted(rows, key=lambda r: r["k"])
    full, spills = _sort_rows(rows, exprs)
    assert not spills and full == want
    spilled, spills = _sort_rows(rows, exprs, budget=60_000, chunk=500)
    assert spills > 0, "budget must force spills"
    assert spilled == want


# -- group-reduce ------------------------------------------------------------------

_N = 700


def _group_keys(kind):
    rng = np.random.default_rng(5)
    ids = rng.integers(0, 23, _N)
    if kind == "int64":
        return pa.array(ids * 1_000_003 - 7, pa.int64())
    if kind == "string":
        return pa.array([f"name-{k:03d}" for k in ids])
    if kind == "decimal":
        return pa.array([Decimal(int(k) * 37 - 300) / 100 for k in ids],
                        pa.decimal128(7, 2))
    if kind == "float64":
        # doubles that differ below float32's grain: one group each only
        # by their exact bits
        return pa.array(1.0 + ids * 2.0 ** -40, pa.float64())
    assert kind == "nullable"
    return pa.array(ids, pa.int64(), mask=ids % 5 == 2)


@pytest.mark.parametrize("where", ["plain", "in-branch"])
@pytest.mark.parametrize("merge", [False, True], ids=["update", "merge"])
@pytest.mark.parametrize("kind", ["int64", "string", "decimal", "float64",
                                  "nullable"])
def test_group_reduce_body_matches_a_dictionary_group_by(kind, merge,
                                                         where):
    """sum, count and max of a nullable int64 per key over the live rows
    of a padded batch — as raw inputs (`update`) and as partial states
    to be merged — groups in key order, nulls first; and the same from
    the forms the body takes as a branch of a `lax.cond`
    (ops/segments.py `inside_branch`)."""
    import contextlib
    from auron_tpu.ops.agg.exec import _group_reduce_body
    from auron_tpu.ops.agg.functions import make_spec
    from auron_tpu.ops.segments import inside_branch
    rng = np.random.default_rng(6)
    value = pa.array(rng.integers(-1000, 1000, _N), pa.int64(),
                     mask=rng.random(_N) < 0.2)
    count = pa.array(rng.integers(0, 9, _N), pa.int64())
    mask = rng.random(_N) < 0.6
    with conf.scoped({"auron.sort.f64.exactbits": "on"}):
        b = Batch.from_arrow(pa.record_batch(
            {"key": _group_keys(kind), "v": value, "c": count}))
        key, v, c = b.columns
        live = jnp.logical_and(b.row_mask(), jnp.asarray(
            np.pad(mask, (0, b.capacity - _N))))
        specs = [make_spec("sum", I64, I64, "s"),
                 make_spec("count", I64, I64, "n"),
                 make_spec("max", I64, I64, "m")]
        with inside_branch() if where == "in-branch" else \
                contextlib.nullcontext():
            out_cols, n_groups = _group_reduce_body(
                [key], [[v], [c] if merge else [v], [v]], live, specs,
                ((True, True),), merge)
        schema = Schema((Field("key", b.schema.fields[0].dtype),
                         *(f for s in specs for f in s.state_fields())))
        got = Batch(schema, out_cols, int(n_groups),
                    b.capacity).to_arrow().to_pylist()
    want = {}
    for k, x, n, ok in zip(_group_keys(kind).to_pylist(),
                           value.to_pylist(), count.to_pylist(), mask):
        if not ok:
            continue
        s, cnt, m = want.get(k, (None, 0, None))
        if x is not None:
            s, m = (s or 0) + x, x if m is None else max(m, x)
        want[k] = (s, cnt + (n if merge else x is not None), m)
    assert {r["key"]: (r["s#sum"], r["n#count"], r["m#max"])
            for r in got} == want
    keys = [r["key"] for r in got]
    assert len(keys) == len(want) and keys == sorted(
        keys, key=lambda k: (k is not None, k))

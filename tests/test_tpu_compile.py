"""Ask the TPU's compiler, without the TPU.

libtpu is installed in the CPU sandbox and compiles for a chip that is
described, not attached (the on-chip-measurement guide, section 2,
rehearsal 3).  These tests lower the kernel families the stage tracer calls
on TPC-DS q01/q07/q19 for one v5e chip, from `ShapeDtypeStruct`s.  What
XLA:TPU would refuse on the chip it refuses here, at no chip time.
A compile that passes is not a chip run: nothing executes, so nothing here
says anything about results or speed (`python chip_smoke.py` does).

Capacities: `CAP` is the bucket dsdgen-SF1-cardinality `store_sales`
(3,000,000 rows) lands in, 2^22.  The sort-bearing families stay at
`SORT_CAP` = 2^13 rows: XLA:TPU's sort compile grows with capacity (one
stable u64 argsort with the engine's int32 row numbers, compiled here for
v5e: 7 s at 2^14, 34 s at 2^17, 43 s at 2^20, 52 s at 2^22; with
`jnp.argsort`'s int64 ones 13 s, 60 s, 99 s, 111 s — CHANGES.md, PR 22),
and a test is kept to a few seconds.

Every job has one kernel, the one the chip runs, so these compile what the
suite runs — but for the three float64 capability sites (exprs/hashing.py
`f64_bits_u32_pair`, ops/sort_keys.py `_orderable_u64_from_f64` and
`f64_bits_of_column`): `jax.default_backend()` still answers "cpu" here
and they would take the arm with a 64-bit bitcast, which XLA:TPU does not
have.  The `tpu_branches` fixture patches `jax.default_backend` for them
and forces the exact-bits sidecar on.  The topology is described inside a
module-scoped fixture — never at import, in a `skipif` or in `parametrize`
arguments: only one process may load libtpu, and every xdist worker imports
this file.  All compiles run in this test's own process, in this one file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from auron_tpu.columnar.batch import DeviceColumn, bucket_capacity
from auron_tpu.config import conf
from auron_tpu.ir.schema import DataType

SF1_STORE_SALES_ROWS = 3_000_000
CAP = bucket_capacity(SF1_STORE_SALES_ROWS)
SORT_CAP = 1 << 13      # largest capacity at which the sort families
#                         compile in a few seconds (module docstring)
BUILD_CAP = 1 << 18     # a dimension-side build table (agg capacity hint)

I32, I64, F64 = DataType.int32(), DataType.int64(), DataType.float64()

# what `auto` resolves to when the backend is a TPU
TPU_OPTIONS = {"auron.sort.f64.exactbits": "on"}


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def tpu_branches(monkeypatch):
    """Steer the float64 capability sites into their TPU arms."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with conf.scoped(TPU_OPTIONS):
        yield


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip can be written to JAX's persistent
    cache but never read back without the chip; keep these silent and
    uncached whatever the environment says."""
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _shape(one_chip, n, dtype):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=one_chip)


def _column(one_chip, dt, cap, exact_bits=False):
    """A DeviceColumn of shapes: data, validity and — for ingested f64 —
    the exact-bits sidecar."""
    return DeviceColumn(
        dt, _shape(one_chip, cap, dt.numpy_dtype()),
        _shape(one_chip, cap, jnp.bool_),
        _shape(one_chip, cap, jnp.uint64) if exact_bits else None)


def _compile(fn, *args):
    """Lower the raw function (jitcheck is armed in the suite: never a
    site's wrapper) and compile it for the described chip."""
    compiled = jax.jit(fn).lower(*args).compile()
    assert compiled.memory_analysis() is not None
    return compiled


# ---------------------------------------------------------------------------
# XLA:TPU — the stage tracer's kernel families
# ---------------------------------------------------------------------------

def test_murmur3_hash_and_pmod_at_sf1_capacity(one_chip, tpu_branches,
                                               no_persistent_cache):
    """The exchange/partition hash (parallel/stage.py `_exchange`):
    murmur3 + pmod over an int64 key, and over an f64 key through its
    exact-bits sidecar (exprs/hashing.py hashes f32 bits otherwise)."""
    from auron_tpu.exprs import hashing as H

    def pid(col):
        return H.pmod(H.hash_columns([col], seed=42), 200)
    _compile(pid, _column(one_chip, I64, CAP))
    _compile(pid, _column(one_chip, F64, CAP, exact_bits=True))
    _compile(pid, _column(one_chip, F64, CAP))      # device-computed f64


def test_f64_key_order_encoding_exact_bits_at_sf1_capacity(
        one_chip, tpu_branches, no_persistent_cache):
    """ORDER BY on float64 money columns (q01, q19): the u64 key words
    come from the ingest-captured bits, or from a pure-integer f32->f64
    widening for device-computed sums — no 64-bit bitcast either way."""
    from auron_tpu.ops.sort_keys import (
        encode_sort_keys, f64_exact_bits_enabled,
    )
    assert f64_exact_bits_enabled()

    def words(col):
        return encode_sort_keys([col], [(False, True)])
    _compile(words, _column(one_chip, F64, CAP, exact_bits=True))
    _compile(words, _column(one_chip, F64, CAP))


def test_join_probe_and_pair_expansion_at_sf1_capacity(
        one_chip, tpu_branches, no_persistent_cache):
    """A fact-side probe of a sorted-hash build table: the two-seed key
    hash, the double searchsorted range lookup and the pair expansion
    (ops/joins/kernel.py) at fact capacity against a dimension-sized
    build side."""
    from auron_tpu.ops.joins.kernel import (
        expand_pairs, join_key_hash, probe_ranges,
    )

    def ranges(pkey, sorted_hashes, plive):
        ph, pvalid = join_key_hash([pkey], CAP)
        return probe_ranges(sorted_hashes, ph, pvalid, plive)
    _compile(ranges, _column(one_chip, I64, CAP),
             _shape(one_chip, BUILD_CAP, jnp.uint64),
             _shape(one_chip, CAP, jnp.bool_))
    _compile(lambda lo, counts: expand_pairs(lo, counts, 0, CAP),
             _shape(one_chip, CAP, jnp.int32),
             _shape(one_chip, CAP, jnp.int64))


@pytest.mark.parametrize("dtype", [jnp.float64, jnp.int64])
def test_sorted_segment_sum_at_sf1_capacity(one_chip, tpu_branches,
                                            no_persistent_cache, dtype):
    """The segment sum behind every SUM/AVG/COUNT of the sort-based
    group-reduce (ops/segments.py).  Its float form was the compile
    blow-up of this path: as an unrolled associative scan it took 124 s
    at 2^20 rows and 376 s at 2^21; as a rolled loop it is seconds at
    any size — which is why this test can afford the real capacity."""
    from auron_tpu.ops.segments import sorted_segment_sum
    _compile(lambda x, seg: sorted_segment_sum(x, seg, CAP),
             _shape(one_chip, CAP, dtype), _shape(one_chip, CAP, jnp.int32))


@pytest.mark.parametrize("dtype", [jnp.float64, jnp.int64])
def test_segmented_running_extreme_at_sf1_capacity(
        one_chip, tpu_branches, no_persistent_cache, dtype):
    """The same rolled scan under MIN/MAX: sorted-segment extremes of the
    group-reduce and the window operator's running min/max
    (ops/window/exec.py) both go through `segmented_running`."""
    from auron_tpu.ops.segments import segmented_running
    _compile(lambda x, first: segmented_running(x, first, True),
             _shape(one_chip, CAP, dtype), _shape(one_chip, CAP, jnp.bool_))


def test_multipass_key_sort(one_chip, tpu_branches, no_persistent_cache):
    """The composed stable single-key argsorts the chip runs instead of
    one multi-operand comparator sort (ops/sort_keys.py), over a nullable
    int64 key and a nullable exact-bits f64 key: u32 rank words and u64
    value words."""
    from auron_tpu.ops.sort_keys import (
        encode_sort_keys, lexsort_indices_live,
    )

    def order(k1, k2, live):
        words = encode_sort_keys([k1, k2], [(True, True), (False, False)])
        return lexsort_indices_live(words, live)
    _compile(order, _column(one_chip, I64, SORT_CAP),
             _column(one_chip, F64, SORT_CAP, exact_bits=True),
             _shape(one_chip, SORT_CAP, jnp.bool_))


@pytest.mark.parametrize("merge", [False, True],
                         ids=["partial-update", "final-merge"])
def test_sort_based_group_reduce(one_chip, tpu_branches,
                                 no_persistent_cache, merge):
    """The sort-based group-reduce of q07's shape (one key; avg, avg,
    count) as the stage tracer calls it (ops/agg/exec.py
    `_group_reduce_body`), update and merge forms."""
    from auron_tpu.ops.agg.exec import _group_reduce_body
    from auron_tpu.ops.agg.functions import make_spec
    specs = [make_spec("avg", F64, F64, "agg1"),
             make_spec("avg", F64, F64, "agg2"),
             make_spec("count", I32, I64, "cnt")]

    def state_cols(spec):
        if not merge:
            return [_column(one_chip, spec.in_dtype, SORT_CAP,
                            exact_bits=spec.in_dtype == F64)]
        return [_column(one_chip, f.dtype, SORT_CAP)
                for f in spec.state_fields()]

    def reduce(key, vcols, live):
        return _group_reduce_body([key], vcols, live, specs,
                                  ((True, True),), merge)
    _compile(reduce, _column(one_chip, I64, SORT_CAP),
             [state_cols(s) for s in specs],
             _shape(one_chip, SORT_CAP, jnp.bool_))


def test_compact_gather_permutation(one_chip, tpu_branches,
                                    no_persistent_cache):
    """The live-rows-to-the-front permutation of the two-phase compact
    gather and of join-chain compaction (parallel/stage.py
    `_live_first_perm`: a bool-key sort with an int32 payload), then the
    column gather."""
    from auron_tpu.parallel.stage import _live_first_perm

    def compact(col, live):
        perm = _live_first_perm(live)
        ok = jnp.take(live, perm)
        return col.gather(perm, ok), ok
    _compile(compact, _column(one_chip, F64, SORT_CAP, exact_bits=True),
             _shape(one_chip, SORT_CAP, jnp.bool_))


@pytest.mark.parametrize("cap", [CAP, BUILD_CAP],
                         ids=["sf1-capacity", "agg-capacity-hint"])
def test_integer_segment_sum_inside_a_conditional(one_chip, tpu_branches,
                                                  no_persistent_cache, cap):
    """COUNT and decimal SUM on either side of an aggregate's choice of
    input (parallel/stage.py `_do_agg`): the int64 segment sum as a
    branch of a `lax.cond`.  With `jnp.cumsum` inside the branch XLA:TPU
    refuses the program at 2^22 rows and does not finish it at 2^18
    (ops/segments.py `inside_branch`); the blocked form compiles in
    seconds."""
    from jax import lax
    from auron_tpu.ops.segments import inside_branch, sorted_segment_sum

    def either(pick, x, seg):
        with inside_branch():
            return lax.cond(pick,
                            lambda: sorted_segment_sum(x, seg, cap),
                            lambda: x)
    _compile(either, jax.ShapeDtypeStruct((), jnp.bool_, sharding=one_chip),
             _shape(one_chip, cap, jnp.int64),
             _shape(one_chip, cap, jnp.int32))


LADDER_CAP = 1 << 20    # the capacity ladder's first rung past the hint


def test_segment_bounds_inside_a_conditional(one_chip, tpu_branches,
                                             no_persistent_cache):
    """Segment bounds from the ids alone (ops/segments.py
    `segment_bounds`: two scatters of row numbers, 32 bits throughout)
    and two integer sums over them, as a branch of a `lax.cond` at
    `tpcds-sf10.q01`'s rung: it compiles, and what it compiles to holds
    no loop — the two binary searches it replaced were 21 dependent
    1,048,576-index gathers each, for every reduction."""
    from jax import lax
    from auron_tpu.ops.segments import (
        inside_branch, segment_bounds, sorted_segment_sum,
    )

    def sums(x, seg):
        bounds = segment_bounds(seg, LADDER_CAP)
        return (sorted_segment_sum(x, bounds, LADDER_CAP),
                sorted_segment_sum(jnp.ones_like(x), bounds, LADDER_CAP))

    def either(pick, x, seg):
        with inside_branch():
            return lax.cond(pick, lambda: sums(x, seg), lambda: (x, x))
    compiled = _compile(
        either, jax.ShapeDtypeStruct((), jnp.bool_, sharding=one_chip),
        _shape(one_chip, LADDER_CAP, jnp.int64),
        _shape(one_chip, LADDER_CAP, jnp.int32))
    assert " while(" not in compiled.as_text()


def test_live_row_compaction_at_sf1_capacity(one_chip, tpu_branches,
                                             no_persistent_cache):
    """An aggregate's input brought down to the capacity its output is cut
    to (parallel/stage.py `_compact_front`): an int32 running count over
    the fact capacity, one scatter of row numbers into `BUILD_CAP` slots
    and one `BUILD_CAP`-index gather a column — no sort, so it can afford
    the real capacities."""
    from auron_tpu.ir.schema import Field, Schema
    from auron_tpu.parallel.stage import DeviceTable, _compact_front

    def compact(key, amount, live):
        t = DeviceTable(Schema((Field("k", I64), Field("a", F64))),
                        [key, amount], live)
        n_live = jnp.sum(live.astype(jnp.int32))
        out = _compact_front(t, n_live, BUILD_CAP)
        return out.cols, out.live
    compiled = _compile(compact, _column(one_chip, I64, CAP),
                        _column(one_chip, F64, CAP, exact_bits=True),
                        _shape(one_chip, CAP, jnp.bool_))
    # the instruction, not the word: the text's table of function names
    # holds whoever first traced a shared inner jit (`jnp.where` inside
    # `sorted_segment_sum`, in the test above)
    assert " sort(" not in compiled.as_text()


RUNGS = (1 << 13, 1 << 15, 1 << 16)     # the ladder's rungs the cells reach


def test_aggregate_body_at_every_rung_inside_a_switch(one_chip, tpu_branches,
                                                      no_persistent_cache):
    """An aggregate's choice of width (parallel/stage.py `_do_agg`): the
    live rows of a `LADDER_CAP`-row table compacted to a rung, the final
    body there — an int64 sum, the 128-bit sum and count of a decimal
    average with its division, a count — and the groups padded back, each
    rung a branch of one `lax.switch`: the hint's rungs of 8,192 and
    65,536 rows and 32,768, the rung of `tpcds-sf10.q01`'s 1,048,576.  A
    kernel under a conditional is another compile than the same kernel
    outside one (ops/segments.py `inside_branch`)."""
    from jax import lax
    from auron_tpu.columnar.batch import DeviceDecimal128Column
    from auron_tpu.ir.schema import Field, Schema
    from auron_tpu.ops.agg.exec import _group_reduce_body
    from auron_tpu.ops.agg.functions import make_spec
    from auron_tpu.ops.segments import inside_branch
    from auron_tpu.parallel.stage import DeviceTable, _compact_front
    money, total = DataType.decimal(7, 2), DataType.decimal(17, 2)
    specs = [make_spec("sum", money, total, "total"),
             make_spec("avg", total, DataType.decimal(21, 6), "mean",
                       wide=True),
             make_spec("count", I64, I64, "cnt")]
    fields = [Field("k", I64)] + [f for s in specs for f in s.state_fields()]

    def column(dt):
        if not dt.is_wide_decimal:
            return _column(one_chip, dt, LADDER_CAP)
        return DeviceDecimal128Column(
            dt, _shape(one_chip, LADDER_CAP, jnp.int64),
            _shape(one_chip, LADDER_CAP, jnp.uint64),
            _shape(one_chip, LADDER_CAP, jnp.bool_))

    def at(rung, t, n_live):
        def side():
            c = _compact_front(t, n_live, rung)
            states, off = [], 1
            for s in specs:
                k = len(s.state_fields())
                states.append(c.cols[off:off + k])
                off += k
            out, n_groups = _group_reduce_body(
                c.cols[:1], states, c.live, specs, ((True, True),), True)
            finals, off = [out[0]], 1
            for s in specs:
                k = len(s.state_fields())
                finals.append(s.eval_final(out[off:off + k]))
                off += k
            return jax.tree.map(
                lambda x: jnp.pad(x, (0, LADDER_CAP - rung)), finals), \
                n_groups
        return side

    def either(cols, live):
        t = DeviceTable(Schema(tuple(fields)), cols, live)
        n_live = jnp.sum(live.astype(jnp.int32))
        with inside_branch():
            return lax.switch(
                sum((n_live > r).astype(jnp.int32) for r in RUNGS[:-1]),
                [at(r, t, n_live) for r in RUNGS])
    _compile(either, [column(f.dtype) for f in fields],
             _shape(one_chip, LADDER_CAP, jnp.bool_))


def test_join_chain_rung_sides_inside_a_switch(one_chip, tpu_branches,
                                               no_persistent_cache):
    """A join chain's choice of width (parallel/stage.py `_join_chain`): the
    live rows of a `CAP`-row table compacted to a rung — a sixty-fourth or
    an eighth of it — or left as they are, and a later join's probe half
    there, each width a branch of one `lax.switch`: the direct gather and
    the search with its exact-key filter under the `lax.cond` on `dense`,
    then the join's output — a string of the build side's among it, as
    columns of 32-bit words — padded back to `CAP`.  The build half (the
    key range, the direct table or the sorted hashes of a `SORT_CAP`-row
    build side, the duplicate-key trip) lies before the switch."""
    from jax import lax
    from auron_tpu.columnar.batch import DeviceStringColumn
    from auron_tpu.ir.schema import Field, Schema
    from auron_tpu.ops.segments import inside_branch
    from auron_tpu.parallel.stage import (
        DeviceTable, _StageTracer, _as_bytes, _as_words, _chain_rungs,
        _compact_front, _pad_rows, _take_rows,
    )
    tracer = _StageTracer(None, {}, None, 1, {})
    text = DataType.string()
    probe_schema = Schema((Field("k", I64), Field("a", F64)))
    build_schema = Schema((Field("bk", I64), Field("w", F64),
                           Field("id", text)))
    rungs = _chain_rungs(CAP)
    assert rungs == [CAP // 64, CAP // 8]

    def chain(cols, live, bcols, blive):
        t = DeviceTable(probe_schema, cols, live)
        build = DeviceTable(build_schema, bcols, blive)
        c = tracer._chain_build_half("join", build, bcols[:1], [I64])
        n_live = jnp.sum(live.astype(jnp.int32))

        def at(width):
            def side():
                probe = t if width == CAP else \
                    _compact_front(t, n_live, width)
                bidx, ok = tracer._chain_lookup(probe, probe.cols[:1], c)
                out = tracer._join_emit(probe, build, bidx, ok, "inner",
                                        "exists", take_build=_take_rows)
                return _pad_rows((_as_words(out.cols), out.live), CAP)
            return side
        with inside_branch():
            words, live = lax.switch(
                sum((n_live > r).astype(jnp.int32) for r in rungs),
                [at(r) for r in rungs + [CAP]])
        return _as_bytes(words), live, c.trip
    compiled = _compile(
        chain, [_column(one_chip, I64, CAP),
                _column(one_chip, F64, CAP, exact_bits=True)],
        _shape(one_chip, CAP, jnp.bool_),
        [_column(one_chip, I64, SORT_CAP), _column(one_chip, F64, SORT_CAP),
         DeviceStringColumn(
             text, jax.ShapeDtypeStruct((SORT_CAP, 16), jnp.uint8,
                                        sharding=one_chip),
             _shape(one_chip, SORT_CAP, jnp.int32),
             _shape(one_chip, SORT_CAP, jnp.bool_))],
        _shape(one_chip, SORT_CAP, jnp.bool_))
    text = compiled.as_text()
    # the switch's three sides and each side's choice of probe
    assert text.count(" conditional(") >= 4

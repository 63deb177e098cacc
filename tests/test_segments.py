"""sorted_segment_* vs jax.ops.segment_* equivalence (fuzzed)."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from auron_tpu.ops import segments


def _rand_sorted_seg(rng, n, max_segs):
    seg = np.sort(rng.integers(0, max_segs, n)).astype(np.int32)
    return jnp.asarray(seg)


@pytest.mark.parametrize("n,num_segments", [(0, 4), (1, 1), (17, 5),
                                            (256, 256), (1000, 37),
                                            (1000, 2000)])
def test_sorted_segment_sum_int(n, num_segments):
    rng = np.random.default_rng(n + num_segments)
    x = jnp.asarray(rng.integers(-100, 100, n).astype(np.int64))
    seg = _rand_sorted_seg(rng, n, num_segments)
    got = segments.sorted_segment_sum(x, seg, num_segments)
    exp = jax.ops.segment_sum(x, seg, num_segments=num_segments)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(exp))


@pytest.mark.parametrize("n,num_segments", [(17, 5), (1000, 37), (4096, 512)])
def test_sorted_segment_sum_float(n, num_segments):
    rng = np.random.default_rng(n)
    x = jnp.asarray(rng.normal(0, 10, n))
    seg = _rand_sorted_seg(rng, n, num_segments)
    got = segments.sorted_segment_sum(x, seg, num_segments)
    exp = jax.ops.segment_sum(x, seg, num_segments=num_segments)
    np.testing.assert_allclose(np.asarray(got), np.asarray(exp),
                               rtol=1e-9, atol=1e-7)


@pytest.mark.parametrize("op,ref", [
    (segments.sorted_segment_min, jax.ops.segment_min),
    (segments.sorted_segment_max, jax.ops.segment_max),
])
@pytest.mark.parametrize("dtype", [np.int64, np.float64])
def test_sorted_segment_extremes(op, ref, dtype):
    rng = np.random.default_rng(5)
    n, num_segments = 1000, 64
    if np.issubdtype(dtype, np.integer):
        x = jnp.asarray(rng.integers(-1000, 1000, n).astype(dtype))
    else:
        x = jnp.asarray(rng.normal(0, 10, n).astype(dtype))
    seg = _rand_sorted_seg(rng, n, num_segments)
    got = np.asarray(op(x, seg, num_segments))
    exp = np.asarray(ref(x, seg, num_segments=num_segments))
    # compare only non-empty segments: identities differ (inf vs dtype max)
    present = np.isin(np.arange(num_segments), np.asarray(seg))
    np.testing.assert_array_equal(got[present], exp[present])
    # empty segments: our identity convention
    fill = segments._extreme_identity(x.dtype,
                                      op is segments.sorted_segment_min)
    assert (got[~present] == fill).all() or not (~present).any()


def test_all_rows_one_segment():
    x = jnp.arange(100, dtype=jnp.int64)
    seg = jnp.zeros(100, jnp.int32)
    assert int(segments.sorted_segment_sum(x, seg, 1)[0]) == 4950
    assert int(segments.sorted_segment_min(x, seg, 1)[0]) == 0
    assert int(segments.sorted_segment_max(x, seg, 1)[0]) == 99


def test_each_row_own_segment():
    x = jnp.asarray(np.array([5, -3, 7], np.int64))
    seg = jnp.asarray(np.array([0, 1, 2], np.int32))
    np.testing.assert_array_equal(
        np.asarray(segments.sorted_segment_sum(x, seg, 3)), [5, -3, 7])


@pytest.mark.parametrize("n", [1, 2, 17, 1000, 4096, 5001])
def test_segmented_scan_equals_a_per_segment_numpy_scan(n):
    """The segmented scan (a rolled doubling loop on every backend:
    XLA:TPU compiles the unrolled associative scan superlinearly in n)
    against numpy's running sum / min / max of each segment alone: float
    sums to tolerance — the association differs — with exact-zero
    segments exactly zero, integer sums and min/max bit-equal."""
    rng = np.random.default_rng(n)
    seg = np.sort(rng.integers(0, max(n // 7, 1), n)).astype(np.int32)
    is_first = np.concatenate([[True], seg[1:] != seg[:-1]])
    xf = rng.uniform(1e4, 1e6, n)
    xf[seg % 3 == 1] = 0.0                     # whole segments of zeros
    xi = rng.integers(-1000, 1000, n)
    starts = np.flatnonzero(is_first)

    def per_segment(x, ufunc):
        return np.concatenate([ufunc.accumulate(part)
                               for part in np.split(x, starts[1:])])

    def scan(x, op):
        return np.asarray(jax.jit(
            lambda a, f: segments._segmented_scan(a, f, op))(
                jnp.asarray(x), jnp.asarray(is_first)))

    got = scan(xf, jnp.add)
    np.testing.assert_allclose(got, per_segment(xf, np.add), rtol=1e-12)
    assert (got[seg % 3 == 1] == 0.0).all()
    for x, op, ufunc in [(xi, jnp.add, np.add),
                         (xf, jnp.minimum, np.minimum),
                         (xi, jnp.maximum, np.maximum)]:
        np.testing.assert_array_equal(scan(x, op), per_segment(x, ufunc))


@pytest.mark.slow   # PR 18 tier-1 re-split (7.6s; exactness property
#   — the deterministic segment-sum units keep the family fast)
def test_sorted_segment_sum_exact_zero_segments():
    """Round-3 regression (q74-shape): an all-zero float segment embedded
    among large-magnitude segments must sum to EXACTLY 0.0 — the
    global-cumsum-difference form returned ~1e-10 residuals, flipping
    `sum > 0` filters and exploding y2/y1 ratios."""
    import numpy as np
    import jax.numpy as jnp
    from auron_tpu.ops.segments import sorted_segment_sum

    rng = np.random.default_rng(11)
    segs, vals = [], []
    for s in range(64):
        n = int(rng.integers(50, 200))
        segs.append(np.full(n, s))
        if s % 7 == 3:
            vals.append(np.zeros(n))             # exact-zero segment
        else:
            vals.append(rng.uniform(1e4, 1e6, n))
    seg = jnp.asarray(np.concatenate(segs), jnp.int32)
    x = jnp.asarray(np.concatenate(vals), jnp.float64)
    got = np.asarray(sorted_segment_sum(x, seg, 64))
    for s in range(64):
        expect = float(np.concatenate(vals)[np.concatenate(segs) == s].sum())
        if s % 7 == 3:
            assert got[s] == 0.0, f"segment {s}: {got[s]!r} != exact 0.0"
        else:
            assert abs(got[s] - expect) < 1e-6 * max(1.0, abs(expect))


@pytest.mark.parametrize("n", [1, 2047, 2048, 2049, 8192, 4 * 2570])
@pytest.mark.parametrize("dtype", [np.int64, np.int32])
def test_integer_running_sum_inside_a_branch_is_the_same_sum(n, dtype):
    """`inside_branch` gives a 64-bit running sum its blocked form (what
    XLA:TPU compiles inside a `lax.cond`): the same numbers, a wrap
    included, at sizes that are and are not whole blocks; int32 keeps
    `jnp.cumsum`; and a sorted segment sum through either is one sum."""
    rng = np.random.default_rng(n)
    info = np.iinfo(dtype)
    x = rng.integers(info.min // 4, info.max // 4, n).astype(dtype)
    x[:8] = info.max // 2                 # the running sum wraps
    want = np.cumsum(x, dtype=dtype)
    # a function of its own each time: jax keeps a function's trace
    plain = jax.make_jaxpr(lambda v: segments._int_cumsum(v))(x)
    with segments.inside_branch():
        got = np.asarray(segments._int_cumsum(jnp.asarray(x)))
        blocked = jax.make_jaxpr(lambda v: segments._int_cumsum(v))(x)
    assert got.dtype == dtype and np.array_equal(got, want)
    assert (str(blocked) != str(plain)) == (dtype is np.int64)
    assert not getattr(segments._TRACE_MODE, "branch", 0)
    seg = jnp.asarray(np.sort(rng.integers(0, 16, n)).astype(np.int32))
    sums = np.asarray(segments.sorted_segment_sum(jnp.asarray(x), seg, 16))
    with segments.inside_branch():
        same = np.asarray(
            segments.sorted_segment_sum(jnp.asarray(x), seg, 16))
    assert np.array_equal(sums, same)


# -- bounds known, not searched -----------------------------------------------

def _searched_bounds(seg, num_segments):
    """What `_segment_ranges` read before PR 36: two binary searches."""
    sids = np.arange(num_segments)
    starts = np.searchsorted(seg, sids, side="left")
    ends = np.searchsorted(seg, sids, side="right")
    return starts, ends, ends > starts


def _seg_case(case):
    """(ascending ids, num_segments) of one shape of `seg`."""
    rng = np.random.default_rng(len(case))
    return {
        # ids drawn with repeats out of 300: gaps between the segments
        "gaps": (np.sort(rng.integers(0, 300, 500)), 300),
        # nothing below 40 nor above 59 of 100
        "empty-leading-and-trailing": (
            np.sort(rng.integers(40, 60, 257)), 100),
        "one-segment": (np.zeros(64, np.int64), 1),
        "one-segment-of-many": (np.full(64, 7), 64),
        "every-row-its-own": (np.arange(128), 128),
        # an aggregate over no live row: all in the padding's segment
        "all-padding": (np.full(256, 255), 256),
        # live groups, then the padding's segment capacity - 1
        "groups-then-padding": (
            np.concatenate([np.repeat(np.arange(20), 5),
                            np.full(28, 127)]), 128),
        "n-1": (np.zeros(1, np.int64), 1),
        "n-1-last-of-many": (np.full(1, 4), 5),
        "more-segments-than-rows": (np.sort(rng.integers(0, 2000, 100)),
                                    2000),
    }[case]


_SEG_CASES = ["gaps", "empty-leading-and-trailing", "one-segment",
              "one-segment-of-many", "every-row-its-own", "all-padding",
              "groups-then-padding", "n-1", "n-1-last-of-many",
              "more-segments-than-rows"]


@pytest.mark.parametrize("branch", [False, True], ids=["plain", "branch"])
@pytest.mark.parametrize("case", _SEG_CASES)
def test_bounds_from_the_boundaries_equal_the_searched_ones(case, branch):
    """`segment_bounds` against the two `searchsorted` it replaced:
    `nonempty` for every segment, `starts` and `ends` wherever it holds;
    an empty segment reads [0, 0).  And the three reductions given the
    bounds return what they return given the ids."""
    ids, num_segments = _seg_case(case)
    seg = jnp.asarray(ids.astype(np.int32))
    starts, ends, nonempty = _searched_bounds(ids, num_segments)
    with segments.inside_branch() if branch else contextlib.nullcontext():
        got = jax.jit(lambda s: segments.segment_bounds(s, num_segments))(
            seg)
    assert isinstance(got, segments.SegmentBounds)
    assert got.shape == seg.shape
    np.testing.assert_array_equal(np.asarray(got.ids), ids)
    np.testing.assert_array_equal(np.asarray(got.nonempty), nonempty)
    np.testing.assert_array_equal(np.asarray(got.starts)[nonempty],
                                  starts[nonempty])
    np.testing.assert_array_equal(np.asarray(got.ends)[nonempty],
                                  ends[nonempty])
    assert not np.asarray(got.starts)[~nonempty].any()
    assert not np.asarray(got.ends)[~nonempty].any()
    np.testing.assert_array_equal(
        np.asarray(got.is_first()),
        np.concatenate([[True], ids[1:] != ids[:-1]]))
    rng = np.random.default_rng(7)
    for x in (rng.integers(-1000, 1000, ids.shape[0]),
              rng.normal(0, 10, ids.shape[0])):
        x = jnp.asarray(x)
        for op, ref in [
                (segments.sorted_segment_sum, jax.ops.segment_sum),
                (segments.sorted_segment_min, None),
                (segments.sorted_segment_max, None)]:
            alone = np.asarray(op(x, seg, num_segments))
            shared = np.asarray(op(x, got, num_segments))
            np.testing.assert_array_equal(shared, alone)
            if ref is not None and x.dtype == jnp.int64:
                np.testing.assert_array_equal(
                    alone, np.asarray(ref(x, seg, num_segments)))


def test_bounds_are_derived_once_and_counted():
    """Handed to the reductions, bounds are neither derived again nor
    searched for; `counting` counts both, nested or not; bounds of
    another number of segments are refused."""
    seg = jnp.asarray(np.sort(
        np.random.default_rng(3).integers(0, 16, 200)).astype(np.int32))
    x = jnp.arange(200, dtype=jnp.int64)

    def shared(x, seg):
        b = segments.segment_bounds(seg, 16)
        return (segments.sorted_segment_sum(x, b, 16),
                segments.sorted_segment_min(x, b, 16),
                segments.sorted_segment_max(x.astype(jnp.float64), b, 16))

    def alone(x, seg):
        return (segments.sorted_segment_sum(x, seg, 16),
                segments.sorted_segment_min(x, seg, 16))

    with segments.counting() as counted:
        text = str(jax.make_jaxpr(shared)(x, seg))
    assert (counted.bounds, counted.reductions) == (1, 3)
    assert text.count("= scatter[") == 2
    with segments.counting() as counted:
        with segments.counting() as inner:
            text = str(jax.make_jaxpr(alone)(x, seg))
        assert (inner.bounds, inner.reductions) == (2, 2)
        segments.known_bounds(seg, seg[:16], seg[:16])
    assert (counted.bounds, counted.reductions) == (1, 0)
    assert text.count("= scatter[") == 4
    assert getattr(segments._TRACE_MODE, "counter", None) is None
    for traced in (shared, alone):
        assert "while" not in str(jax.make_jaxpr(
            lambda x, seg: traced(x, seg)[0])(x, seg))
    with pytest.raises(ValueError):
        segments.sorted_segment_sum(
            x, segments.segment_bounds(seg, 16), 17)


def test_no_search_is_reachable_from_the_segment_kernels():
    import inspect
    source = inspect.getsource(segments)
    code = [ln.split("#")[0] for ln in source.splitlines()]
    assert not any("searchsorted(" in ln for ln in code)


@pytest.mark.parametrize("branch", [False, True], ids=["plain", "branch"])
@pytest.mark.parametrize("live_rows,groups", [
    (0, 1), (1, 1), (300, 7), (300, 300), (512, 40), (512, 512), (511, 511),
], ids=["none-live", "one-row", "few-groups", "all-distinct", "all-live",
        "all-live-all-distinct", "one-dead-all-distinct"])
def test_an_aggregates_bounds_equal_the_searched_ones(live_rows, groups,
                                                      branch):
    """`_group_segments` (ops/agg/exec.py) says its segments' bounds from
    the boundaries it found — group g from its first row to the next
    group's, the last to `n_live`, the padding's segment `capacity - 1`
    over the dead rows — and they are what a search of its ids finds, with
    no scatter added outside a branch and none beyond the boundary rows'
    inside one."""
    from auron_tpu.columnar.batch import DeviceColumn
    from auron_tpu.ir.schema import DataType
    from auron_tpu.ops.agg.exec import _group_segments
    cap = 512
    rng = np.random.default_rng(live_rows + groups)
    key = rng.permutation(np.arange(cap) % groups).astype(np.int64)
    live = rng.permutation(np.arange(cap) < live_rows)
    if groups == live_rows:
        key = np.arange(cap, dtype=np.int64)

    def run(key, live):
        col = DeviceColumn(DataType.int64(), key, jnp.ones(cap, bool))
        perm, seg, n_groups, _keys = _group_segments(
            [col], live, ((True, True),))
        return perm, seg, n_groups

    marked = segments.inside_branch() if branch else \
        contextlib.nullcontext()
    with segments.counting() as counted, marked:
        jaxpr = jax.make_jaxpr(run)(key, live)
        # (jax keeps a function's trace: this one runs the jaxpr's)
        _perm, seg, n_groups = jax.jit(run)(key, live)
    assert counted.bounds == 1 and counted.reductions == 0
    assert str(jaxpr).count("= scatter[") == (1 if branch else 0)
    assert "while" not in str(jaxpr).replace("while_loop", "")
    ids = np.asarray(seg.ids)
    assert (np.diff(ids) >= 0).all()
    starts, ends, nonempty = _searched_bounds(ids, cap)
    assert int(n_groups) == len(set(key[live].tolist()))
    np.testing.assert_array_equal(np.asarray(seg.nonempty), nonempty)
    np.testing.assert_array_equal(np.asarray(seg.starts)[nonempty],
                                  starts[nonempty])
    np.testing.assert_array_equal(np.asarray(seg.ends)[nonempty],
                                  ends[nonempty])

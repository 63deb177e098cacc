"""sorted_segment_* vs jax.ops.segment_* equivalence (fuzzed)."""

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

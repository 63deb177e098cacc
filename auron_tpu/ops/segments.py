"""Sorted-segment reductions without scatter.

The engine's group-by pipeline (agg/exec.py, parallel/spmd.py, window)
always reduces over SORTED segment ids (they come from a lexsort +
boundary cumsum).  XLA lowers jax.ops.segment_* to scatter-(add|min|max),
which serializes badly on TPU; for sorted ids the same reductions are
expressible with purely gather-shaped ops — cumulative scan along rows,
read at each segment's [start, end) range — the TPU-friendly form
(reference analogue: Auron leans on radix-sorted runs for exactly this
reason, agg/agg_table.rs).

The ranges are known, not searched: a segment starts where the id differs
from the row before and ends where it differs from the row after
(`SegmentBounds`).  They are derived once per `seg` — by the caller that
already holds the boundaries (`known_bounds`: ops/agg/exec.py) or from
the ids alone (`segment_bounds`: two scatters) — and handed to every
reduction over it in `seg`'s place.  A binary search per reduction
(`jnp.searchsorted`: ceil(log2(n + 1)) dependent n-index gathers for each
of starts and ends) was 14-36 % of the stage programs' device time (PR 36).

- sum:  inclusive cumsum; total(s) = csum[end(s)-1] - csum[start(s)-1].
  Integer sums are EXACT even if the running cumsum wraps (modular diff);
  float sums are f64 in SQL semantics, where the cancellation error of
  differencing is ~ulp(global sum) — covered by the differential-test
  tolerances.
- min/max: segmented running min/max via an associative scan with a
  reset-at-segment-start combine, read at end(s)-1.

All functions take 1-D x and require seg ascending (rows of equal seg
contiguous), as an array of ids or as the `SegmentBounds` of one.
Callers with possibly-unsorted ids must keep using jax.ops.segment_*.
Behavior matches jax.ops.segment_{sum,min,max} (empty segments -> 0 /
+inf|max / -inf|min).
"""

from __future__ import annotations

import dataclasses
import threading

import jax
import jax.numpy as jnp

_TRACE_MODE = threading.local()


class inside_branch:
    """Trace-time context: what is traced here becomes a branch of a
    `lax.cond` (the stage program's choice of an aggregate's input,
    parallel/stage.py `_do_agg`).  XLA:TPU cannot compile a 1-D 64-bit
    running sum inside a conditional's branch: ahead of time for a v5e,
    `lax.cond(p, lambda: jnp.cumsum(x), ...)` over int64[4194304] is
    refused (RESOURCE_EXHAUSTED: scoped vmem, in the 64-bit
    reduce-window's third level) and over int64[262144] it had not
    compiled after 40 minutes, where the same `cumsum` outside a branch
    compiles in 7 s (PR 29).  Blocked — a running sum along the rows of a
    [n / 2048, 2048] view, then the same over the rows' totals, down to
    one block of 2,048 — it compiles in 8-9 s at both sizes, so that is
    the form `_int_cumsum` takes here, and only here: outside a branch
    the programs are what they were.  Thread-local, so a task tracing
    on another thread is not marked."""

    def __enter__(self):
        _TRACE_MODE.branch = getattr(_TRACE_MODE, "branch", 0) + 1

    def __exit__(self, *exc):
        _TRACE_MODE.branch -= 1


def in_branch() -> bool:
    return bool(getattr(_TRACE_MODE, "branch", 0))


_CUMSUM_BLOCK = 2048


def _int_cumsum(x):
    """Inclusive running sum of a 1-D integer column, modular on wrap."""
    if not in_branch() or x.dtype.itemsize < 8:
        return jnp.cumsum(x)
    n = x.shape[0]
    pad = -n % _CUMSUM_BLOCK
    if n <= _CUMSUM_BLOCK:
        # one block, always of the one length: which 1-D lengths the
        # compiler takes inside a branch is a matter of trial (2,048 and
        # 128 it takes, 512 it refuses)
        return jnp.cumsum(jnp.pad(x, (0, pad)))[:n]
    rows = jnp.cumsum(
        jnp.pad(x, (0, pad)).reshape(-1, _CUMSUM_BLOCK), axis=1)
    totals = rows[:, -1]
    before = _int_cumsum(totals) - totals
    return (rows + before[:, None]).reshape(-1)[:n]


class counting:
    """Trace-time context: counts the segment bounds derived
    (`bounds`) and the reductions that took them (`reductions`) in what
    is traced here, for the stage program's counter (parallel/stage.py:
    `segment_bounds` / `segment_reductions`).  Thread-local, like
    `inside_branch`."""

    def __enter__(self):
        self.bounds = 0
        self.reductions = 0
        self._outer = getattr(_TRACE_MODE, "counter", None)
        _TRACE_MODE.counter = self
        return self

    def __exit__(self, *exc):
        _TRACE_MODE.counter = self._outer


def _count(what: str) -> None:
    counter = getattr(_TRACE_MODE, "counter", None)
    if counter is not None:
        setattr(counter, what, getattr(counter, what) + 1)


@jax.tree_util.register_dataclass
@dataclasses.dataclass(frozen=True)
class SegmentBounds:
    """Ascending segment ids with every segment's rows [start, end):
    what a sorted-segment reduction reads, computed once and taken in
    `seg`'s place by all of them.  `ids` is int32[n]; `starts` and `ends`
    are int32[num_segments], meaningful where `ends > starts` (an empty
    segment holds whatever makes that false).  A pytree: it crosses a
    `jit` boundary like the array it stands for, and answers `shape` as
    that array does (a count of all rows asks it)."""
    ids: jax.Array
    starts: jax.Array
    ends: jax.Array

    @property
    def shape(self):
        return self.ids.shape

    @property
    def nonempty(self):
        return self.ends > self.starts

    def is_first(self):
        """bool[n]: the row opens its segment (row 0 always does)."""
        return jnp.concatenate(
            [jnp.ones((1,), bool), self.ids[1:] != self.ids[:-1]])


def known_bounds(ids, starts, ends) -> SegmentBounds:
    """The bounds a caller already holds (the aggregate's boundaries:
    ops/agg/exec.py `_group_segments`), counted as derived once."""
    _count("bounds")
    return SegmentBounds(ids, starts, ends)


def segment_bounds(seg, num_segments: int) -> SegmentBounds:
    """Bounds from ascending ids alone, gaps and empty segments included
    (an empty segment reads [0, 0)): a row that differs from the one
    before is its segment's start, one that differs from the one after
    its last, and each such row writes its number to its segment's slot.
    Two scatters of unique indices (every other row is sent past the end,
    each to a slot of its own, and dropped); no loop, no search, 32 bits
    throughout, so it compiles inside a conditional's branch too."""
    if isinstance(seg, SegmentBounds):
        if seg.starts.shape[0] != num_segments:
            raise ValueError(
                f"bounds of {seg.starts.shape[0]} segments handed to a "
                f"reduction over {num_segments}")
        return seg
    _count("bounds")
    n = seg.shape[0]
    ids = seg.astype(jnp.int32)
    rows = jnp.arange(n, dtype=jnp.int32)
    differs = ids[1:] != ids[:-1]
    edge = jnp.ones((1,), bool)
    dropped = num_segments + rows

    def slots(at, values):
        return jnp.zeros((num_segments,), jnp.int32).at[
            jnp.where(at, ids, dropped)].set(
                values, mode="drop", unique_indices=True)

    return SegmentBounds(
        seg, slots(jnp.concatenate([edge, differs]), rows),
        slots(jnp.concatenate([differs, edge]), rows + 1))


def sorted_segment_sum(x, seg, num_segments: int):
    """segment_sum for ascending seg ids (same contract as
    jax.ops.segment_sum(x, seg, num_segments))."""
    if x.shape[0] == 0:
        return jnp.zeros((num_segments,), x.dtype)
    seg = segment_bounds(seg, num_segments)
    _count("reductions")
    starts, ends, nonempty = seg.starts, seg.ends, seg.nonempty
    if jnp.issubdtype(x.dtype, jnp.floating):
        # floats must NOT use the global-cumsum difference: an all-zero
        # segment differencing two ~equal multi-million cumsums comes
        # back as ~1e-10, which flips `sum > 0` predicates (q74-shape
        # year pivots) and explodes ratios.  A segmented scan resets the
        # running sum at each segment start, so a segment's total only
        # ever adds its OWN elements — exact zeros stay exact.
        run = _segmented_scan(x, seg.is_first(), jnp.add)
        total = jnp.take(run, jnp.clip(ends - 1, 0), mode="clip")
        return jnp.where(nonempty, total, jnp.zeros((), x.dtype))
    # integer sums: modular cumsum difference is EXACT even on wrap
    csum = _int_cumsum(x)
    upper = jnp.take(csum, jnp.clip(ends - 1, 0), mode="clip")
    lower = jnp.where(starts > 0,
                      jnp.take(csum, jnp.clip(starts - 1, 0), mode="clip"),
                      jnp.zeros((), x.dtype))
    return jnp.where(nonempty, upper - lower, jnp.zeros((), x.dtype))


def _segmented_scan(x, is_first, op):
    """Inclusive scan of `op` along x that restarts wherever `is_first`
    is set (`is_first[0]` must be): the Hillis-Steele doubling scan of
    the operator (fa, a) . (fb, b) = (fa | fb, b if fb else op(a, b)),
    as a ROLLED loop of log2(n) shift-and-combine steps.

    Rolled, because XLA:TPU compiles the unrolled `lax.associative_scan`
    superlinearly in n — a float64 segmented sum took 124 s at 2^20 rows
    and 376 s at 2^21 (v5e, ahead of time, PR 22), most of q07's 1393 s
    cold compile at 2^22 on the chip — while one loop body compiles in
    seconds at any n.  It does log2(n) streaming passes over the column
    instead of two.  One form on every backend: the CPU suite runs what
    the chip runs.

    Only elements of one segment are ever combined, in index order, so
    exact zeros stay exact."""
    n = x.shape[0]
    pad_val = jnp.zeros_like(x)     # shifted in, never combined: see step
    pad_flag = jnp.zeros_like(is_first)

    def step(i, carry):
        val, flag = carry
        # the element 2^i rows up; rows with no such element shift in
        # (False, 0), and keep their value: their flag is already set,
        # because the window behind them reaches row 0
        off = n - jnp.left_shift(jnp.int32(1), i)
        up_val = jax.lax.dynamic_slice(
            jnp.concatenate([pad_val, val]), (off,), (n,))
        up_flag = jax.lax.dynamic_slice(
            jnp.concatenate([pad_flag, flag]), (off,), (n,))
        return (jnp.where(flag, val, op(up_val, val)),
                jnp.logical_or(flag, up_flag))

    run, _ = jax.lax.fori_loop(0, max(n - 1, 1).bit_length(), step,
                               (x, is_first))
    return run


def segmented_running(x, is_first, op_is_min: bool):
    """Running min/max that resets at segment starts (segmented scan)."""
    return _segmented_scan(x, is_first,
                           jnp.minimum if op_is_min else jnp.maximum)


def _extreme_identity(dtype, op_is_min: bool):
    if jnp.issubdtype(dtype, jnp.floating):
        return jnp.inf if op_is_min else -jnp.inf
    info = jnp.iinfo(dtype)
    return info.max if op_is_min else info.min


def _sorted_segment_extreme(x, seg, num_segments: int, op_is_min: bool):
    fill = _extreme_identity(x.dtype, op_is_min)
    if x.shape[0] == 0:
        return jnp.full((num_segments,), fill, x.dtype)
    seg = segment_bounds(seg, num_segments)
    _count("reductions")
    run = segmented_running(x, seg.is_first(), op_is_min)
    at_end = jnp.take(run, jnp.clip(seg.ends - 1, 0), mode="clip")
    return jnp.where(seg.nonempty, at_end, jnp.asarray(fill, x.dtype))


def sorted_segment_min(x, seg, num_segments: int):
    return _sorted_segment_extreme(x, seg, num_segments, True)


def sorted_segment_max(x, seg, num_segments: int):
    return _sorted_segment_extreme(x, seg, num_segments, False)

"""Hash-based group-id assignment: linear-probed scatter table, no sort.

The sort-based group reduction (`_group_reduce_body`) pays one megarow
lexsort per input batch; on the CPU backend XLA's comparator sort is ~3x
slower than numpy's and dominates the whole query (engine profile,
round 3).  Scatter/gather, by contrast, are FASTER than numpy there — so
the CPU backend groups by building an open-addressing hash table of row
ids (scatter-min + probe rounds), mirroring the reference's hash-map agg
(agg/agg_hash_map.rs:26 — its SIMD probe loop) instead of its
radix-sort shuffle path.  TPU keeps the sort-based kernel: scatters
serialize there (ops/segments.py docstring) and the TPU sort is fast.

Contract (mirrors the sort path's group structure):

    seg, key_src, n_groups = hash_group_structure(words, live)

- `words`: equality-preserving u64 encodings (encode_sort_keys), so
  grouping equality matches the sort path exactly — including the
  truncated-prefix string preorder and canonicalized floats.
- `seg[i]`: dense group id of live row i, in FIRST-WINNER row order;
  dead rows map to the padding segment `capacity-1` (same trick as the
  sort path; padding can never collide with a real group because
  n_groups <= n_live < capacity whenever dead rows exist).
- `key_src`: row index of each group's representative, densely packed
  [0, n_groups) in ascending row order.
- group order is NOT key-sorted: consumers that need sorted runs
  (spill files, the merge-carry loop) must force the sort kernel.
"""

from __future__ import annotations

from typing import Any, List, Tuple

import jax.numpy as jnp
import numpy as np
from jax import lax

_SENT = np.int32(2**31 - 1)


def table_bits_key() -> int:
    """The trace-time config read below, for kernel cache keys (a flag
    flip must not reuse a kernel traced under the old table size)."""
    from auron_tpu.config import conf
    return int(conf.get("auron.agg.hash.table.max.bits"))


def _mix64(h):
    """splitmix64 finalizer (public-domain constant mix)."""
    h = (h ^ (h >> 30)) * jnp.uint64(0xBF58476D1CE4E5B9)
    h = (h ^ (h >> 27)) * jnp.uint64(0x94D049BB133111EB)
    return h ^ (h >> 31)


def hash_group_structure(words: List[Any], live
                         ) -> Tuple[Any, Any, Any]:
    capacity = int(live.shape[0])
    from auron_tpu.config import conf
    max_bits = int(conf.get("auron.agg.hash.table.max.bits"))
    table_size = 1 << max(3, (2 * capacity - 1).bit_length())
    if max_bits > 0:
        # cap the slot spread: scatter-min into a 2^21-slot table thrashs
        # cache and runs ~3x slower than into an L2-resident table
        # (measured 118ms vs 41ms per 1M updates on this CPU backend).
        # A smaller table costs extra probe rounds only when distinct
        # keys exceed the slot count, and those rounds are cheap: done
        # rows scatter non-improving SENT updates (read+compare, no
        # write), measured ~5ms/round vs 40ms for the first.
        table_size = min(table_size, 1 << max_bits)
    h = None
    for w in words:
        hw = _mix64(w.astype(jnp.uint64))
        h = hw if h is None else _mix64(h ^ hw)
    slot0 = (h & jnp.uint64(table_size - 1)).astype(jnp.int32)
    rows = jnp.arange(capacity, dtype=jnp.int32)

    def cond(carry):
        _slot, _owner, done = carry
        return jnp.any(jnp.logical_not(done))

    def body(carry):
        slot, owner, done = carry
        cand = jnp.where(done, _SENT, rows)
        table = jnp.full((table_size,), _SENT, jnp.int32) \
            .at[slot].min(cand, mode="drop")
        win = jnp.take(table, slot)
        ok = jnp.logical_and(jnp.logical_not(done), win != _SENT)
        win_c = jnp.clip(win, 0, capacity - 1)
        for w in words:
            ok = jnp.logical_and(ok, jnp.take(w, win_c) == w)
        owner = jnp.where(ok, win_c, owner)
        done = jnp.logical_or(done, ok)
        slot = jnp.where(done, slot,
                         (slot + 1) & jnp.int32(table_size - 1))
        return slot, owner, done

    # every round resolves at least the globally smallest unresolved
    # row's whole group (it wins its slot), so the loop terminates in
    # <= n_distinct_keys rounds — typically a handful
    _, owner, _ = lax.while_loop(
        cond, body,
        (slot0, jnp.zeros(capacity, jnp.int32), jnp.logical_not(live)))

    mark = jnp.logical_and(live, owner == rows)
    prefix = jnp.cumsum(mark.astype(jnp.int32))
    n_groups = prefix[-1]
    gid_at_winner = prefix - 1
    gid = jnp.take(gid_at_winner, owner)
    seg = jnp.where(live, gid, capacity - 1).astype(jnp.int32)
    key_src = jnp.nonzero(mark, size=capacity, fill_value=0)[0] \
        .astype(jnp.int32)
    return seg, key_src, n_groups


# ---------------------------------------------------------------------------
# one-hot / matmul group reduction (auron.kernel.group.strategy=onehot)
# ---------------------------------------------------------------------------
#
# The scatter-free alternative for UNSORTED segment ids with a SMALL
# static segment count: expand each chunk of rows into a one-hot
# [chunk, G] matrix and reduce it — sums become a [1, chunk] x [chunk, G]
# matmul (MXU work on TPU-class backends, where scatters serialize),
# min/max a chunked masked reduce.  Costs n*G multiply-accumulates, so it
# is a LOW-cardinality strategy by construction; ops/segments.py gates it
# through strategy.group_strategy (auto keeps scatter on CPU — measured
# there: G=64 scatter 158ms vs one-hot 225ms at 4M rows; the MXU is the
# whole point).  Results are deterministic per shape (fixed chunk
# reduction order) but NOT bitwise-equal to the scatter kernel for
# floats — a strategy is self-consistent, not cross-strategy-identical;
# the chaos gate runs each strategy against itself.

_ONEHOT_CHUNK = 8192


def onehot_segment_sum(x, seg, num_segments: int):
    """jax.ops.segment_sum twin (out-of-range seg ids drop) via chunked
    one-hot matmul."""
    n = x.shape[0]
    if n == 0:
        return jnp.zeros((num_segments,), x.dtype)
    chunk = min(_ONEHOT_CHUNK, n)
    pad = (-n) % chunk
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,), x.dtype)])
        # padding (and any out-of-range id) lands outside every one-hot
        # column
        seg = jnp.concatenate(
            [seg, jnp.full((pad,), num_segments, seg.dtype)])
    xr = x.reshape(-1, chunk)
    sr = seg.reshape(-1, chunk)
    gids = jnp.arange(num_segments, dtype=sr.dtype)

    # XLA:TPU's x64 rewrite has no 64-bit integer dot (count/bigint
    # sums): those reduce the masked expansion directly, same n*G work
    wide_int = jnp.issubdtype(x.dtype, jnp.integer) and \
        x.dtype.itemsize > 4

    def body(acc, args):
        xc, sc = args
        oh = sc[:, None] == gids[None, :]
        if wide_int:
            return acc + jnp.sum(jnp.where(oh, xc[:, None], 0),
                                 axis=0), None
        return acc + xc @ oh.astype(x.dtype), None

    acc, _ = lax.scan(body, jnp.zeros((num_segments,), x.dtype), (xr, sr))
    return acc


def onehot_segment_extreme(x, seg, num_segments: int, op_is_min: bool):
    """segment_min/max twin: chunked masked reduce (no matmul — extremes
    don't distribute over +), same empty-segment identities."""
    if jnp.issubdtype(x.dtype, jnp.floating):
        fill = jnp.inf if op_is_min else -jnp.inf
    else:
        info = jnp.iinfo(x.dtype)
        fill = info.max if op_is_min else info.min
    n = x.shape[0]
    if n == 0:
        return jnp.full((num_segments,), fill, x.dtype)
    chunk = min(_ONEHOT_CHUNK, n)
    pad = (-n) % chunk
    if pad:
        x = jnp.concatenate([x, jnp.full((pad,), fill, x.dtype)])
        seg = jnp.concatenate(
            [seg, jnp.full((pad,), num_segments, seg.dtype)])
    xr = x.reshape(-1, chunk)
    sr = seg.reshape(-1, chunk)
    gids = jnp.arange(num_segments, dtype=sr.dtype)

    def body(acc, args):
        xc, sc = args
        oh = sc[:, None] == gids[None, :]
        vals = jnp.where(oh, xc[:, None], jnp.asarray(fill, x.dtype))
        red = jnp.min(vals, axis=0) if op_is_min else \
            jnp.max(vals, axis=0)
        return (jnp.minimum(acc, red) if op_is_min
                else jnp.maximum(acc, red)), None

    acc, _ = lax.scan(body, jnp.full((num_segments,), fill, x.dtype),
                      (xr, sr))
    return acc

"""The shared join kernel: sorted-hash build table + searchsorted probe.

Build:  key columns -> u64 hash (two murmur passes packed) with null-key
        sentinels -> argsort -> (sorted_hashes, perm, build_batch)
Probe:  probe hashes -> lo/hi = searchsorted range -> candidate counts ->
        chunked pair expansion -> exact key verification -> joined batches.

All device work is eager jnp (XLA kernels); chunk sizes are fixed
capacities so shapes stay static.

Kernel strategies (ops/strategy.py, BENCH_r03-r05 floors):

- build sort: `auron.kernel.sort.strategy` routes the hash argsort
  through the radix pack-sort (ops/radix_sort.py) — same permutation,
  2.4x cheaper on the CPU backend at megarow builds.
- probe: `auron.kernel.join.probe.strategy` replaces the double-
  searchsorted range scan with a bucket-PARTITIONED probe index: the
  high radix bits of the u64 key hash select a bucket over the build
  side's DEDUPLICATED sorted hashes, and a bounded binary search runs
  only within that bucket's span (iteration count fixed per build table
  from the measured max span — one host sync at build time).  The
  (lo, counts) it returns are BIT-IDENTICAL to probe_ranges' (leftmost
  position + duplicate count over the same sorted array), so the pair
  expansion, verification and emission downstream are untouched and
  results cannot diverge.  Measured (4M probes, CPU): 3.1x at a 4k
  build, 1.9x at 4M.  Above `auron.kernel.join.partitioned.max.rows`
  the strategy falls back to this sorted searchsorted path.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, List, Optional, Tuple

import jax.numpy as jnp
import numpy as np

from auron_tpu.columnar.batch import (
    Batch, DeviceColumn, DeviceStringColumn, HostColumn, bucket_capacity,
    concat_batches,
)
from auron_tpu.exprs import hashing as H
from auron_tpu.exprs import strings_device as S
from auron_tpu.ir.schema import DataType, Field, Schema
from auron_tpu.runtime import jitcheck

# the probe/pair kernel families are keyed per static-flag combination
# (emit/track/side/b_bits/iters) and reused across every join of that
# shape — key/payload column structures and capacities vary per query
# by DESIGN (jax.jit's per-aval cache holds each signature's program)
jitcheck.waive_retraces(
    "join.range*", 0,
    "one range kernel per flag combination; key structures vary")
jitcheck.waive_retraces(
    "join.pair", 0,
    "one pair kernel per flag combination; column structures vary")
jitcheck.waive_retraces(
    "join.probe_index", 0,
    "keyed per b_bits; build capacities vary per table")

# hash-sentinels: null join keys never match (SQL equi-join semantics)
_NULL_BUILD = np.uint64(0xFFFFFFFFFFFFFFFF)
_NULL_PROBE = np.uint64(0xFFFFFFFFFFFFFFFE)


def _key_validity(c: Any, capacity: int):  # jitcheck: waive (HostColumn arm: trace-time-dead — the fused/jitted paths are all-device; eager callers hit it with concrete arrays)
    if isinstance(c, HostColumn):
        v = np.zeros(capacity, bool)
        v[:len(c.array)] = ~np.asarray(c.array.is_null())
        return jnp.asarray(v)
    return c.validity


def join_key_hash(cols: List[Any], capacity: int):
    """u64 key hash: two chained murmur3 passes with different seeds packed
    into one u64; rows with any null key get a non-matching sentinel."""
    h1 = H.hash_columns(cols, seed=42, capacity=capacity).astype(jnp.uint32)
    h2 = H.hash_columns(cols, seed=0x9747B28C,
                        capacity=capacity).astype(jnp.uint32)
    h = (h1.astype(jnp.uint64) << 32) | h2.astype(jnp.uint64)
    all_valid = _key_validity(cols[0], capacity)
    for c in cols[1:]:
        all_valid = jnp.logical_and(all_valid, _key_validity(c, capacity))
    return h, all_valid


@dataclass
class ProbeIndex:
    """Bucket-partitioned probe accelerator over one BuildTable's sorted
    hashes: the build side's DISTINCT hash values (padded with MAX),
    each with its [start, count) range in the sorted array, plus the
    per-radix-bucket start offsets.  `iters` is the bounded binary
    search's statically-baked iteration count: ceil(log2(max bucket
    span)), host-synced ONCE when the table is built (the only sync the
    partitioned strategy adds, and only when it is chosen)."""
    uvals: Any          # u64[capacity]: sorted distinct hashes, pad=MAX
    ustart: Any         # int32[capacity]: first sorted position of uvals[i]
    ucnt: Any           # int32[capacity]: duplicate count of uvals[i]
    bucket_start: Any   # int32[2^b_bits + 1]: bucket -> first uniq pos
    b_bits: int         # radix width of the bucket id (hash high bits)
    iters: int          # bounded-search iterations (2^iters >= max span)


def _build_probe_index_kernel(b_bits: int):
    """Dedup + bucket-offset program over the sorted hash array.  Cached
    per b_bits; returns max_span as a device scalar for the one-time
    host sync."""
    def run(sorted_hashes):
        cap = sorted_hashes.shape[0]
        uniq_first = jnp.concatenate(
            [jnp.ones(1, bool), sorted_hashes[1:] != sorted_hashes[:-1]])
        n_uniq = jnp.sum(uniq_first.astype(jnp.int32))
        upos = jnp.nonzero(uniq_first, size=cap, fill_value=cap)[0] \
            .astype(jnp.int32)
        arange = jnp.arange(cap, dtype=jnp.int32)
        in_uniq = arange < n_uniq
        uvals = jnp.where(in_uniq,
                          jnp.take(sorted_hashes,
                                   jnp.clip(upos, 0, cap - 1)),
                          jnp.uint64(0xFFFFFFFFFFFFFFFF))
        ustart = jnp.where(in_uniq, upos, cap).astype(jnp.int32)
        unext = jnp.concatenate(
            [ustart[1:], jnp.full((1,), cap, jnp.int32)])
        ucnt = jnp.where(in_uniq, unext - ustart, 0).astype(jnp.int32)
        edges = jnp.arange(1 << b_bits, dtype=jnp.uint64) \
            << np.uint64(64 - b_bits)
        bs = jnp.minimum(jnp.searchsorted(uvals, edges).astype(jnp.int32),
                         n_uniq)
        bs = jnp.concatenate([bs, n_uniq[None].astype(jnp.int32)])
        max_span = jnp.max(bs[1:] - bs[:-1])
        return uvals, ustart, ucnt, bs, max_span
    return run


def build_probe_index(sorted_hashes, b_bits: Optional[int] = None
                      ) -> ProbeIndex:
    """Eager-context builder (host-syncs the max bucket span)."""
    from auron_tpu.ops.kernel_cache import cached_jit, host_sync
    from auron_tpu.ops.strategy import join_bucket_bits
    cap = int(sorted_hashes.shape[0])
    if b_bits is None:
        b_bits = join_bucket_bits(cap)
    k = cached_jit(("join.probe_index", b_bits),
                   lambda: _build_probe_index_kernel(b_bits))
    uvals, ustart, ucnt, bs, max_span = k(sorted_hashes)
    with jitcheck.declared_transfer("join.probe_index.span"):  # jitcheck: waive (the partitioned strategy's ONE build-time sync: bakes the bounded search's static iteration count)
        span = int(host_sync(max_span))
    # span.bit_length() == floor(log2(span)) + 1, the exact iteration
    # count that drives a [lo, hi) lower-bound interval of `span` to
    # size 0.  The previous ceil(log2(span)) form was ONE short exactly
    # when the max bucket span is a power of two (span=2: one iteration
    # can stop at the bucket start and miss a real match one slot
    # right) — surfaced by AQE's broadcast-converted builds, whose
    # small dedup'd tables produce tiny power-of-two spans.
    iters = int(max(span, 1)).bit_length()
    return ProbeIndex(uvals=uvals, ustart=ustart, ucnt=ucnt,
                      bucket_start=bs, b_bits=b_bits, iters=iters)


def bounded_probe(index: ProbeIndex, ph):
    """(lo, counts) for probe hashes `ph` — bit-identical to
    probe_ranges' leftmost-position + range-width over the same sorted
    hash array, computed as bucket dispatch + bounded binary search over
    the deduplicated values."""
    uvals, bs = index.uvals, index.bucket_start
    cap = uvals.shape[0]
    pid = (ph >> np.uint64(64 - index.b_bits)).astype(jnp.int32)
    lo = jnp.take(bs, pid)
    hi = jnp.take(bs, pid + 1)
    for _ in range(index.iters):
        mid = (lo + hi) >> 1
        v = jnp.take(uvals, jnp.clip(mid, 0, cap - 1))
        go_right = jnp.logical_and(lo < hi, v < ph)
        lo, hi = (jnp.where(go_right, mid + 1, lo),
                  jnp.where(jnp.logical_and(lo < hi,
                                            jnp.logical_not(go_right)),
                            mid, hi))
    p = jnp.clip(lo, 0, cap - 1)
    found = jnp.take(uvals, p) == ph
    out_lo = jnp.where(found, jnp.take(index.ustart, p), 0)
    counts = jnp.where(found, jnp.take(index.ucnt, p), 0)
    return out_lo.astype(jnp.int32), counts.astype(jnp.int64)


def probe_ranges_partitioned(index: ProbeIndex, probe_hash, probe_valid,
                             probe_live):
    """Partitioned-strategy twin of probe_ranges (same sentinel
    wrapping, same (lo, counts) contract)."""
    ph = jnp.where(jnp.logical_and(probe_live, probe_valid), probe_hash,
                   _NULL_PROBE)
    return bounded_probe(index, ph)


@dataclass
class BuildTable:
    """The 'hash map': build batch + hash-sorted permutation.  `live`
    marks real rows (the batch may be an UNcompacted device concat of the
    build stream — dead rows carry the null sentinel and never match).
    `probe` is the optional bucket-partitioned probe index (strategy
    'partitioned'); when absent, probes double-searchsorted the sorted
    hashes directly."""
    batch: Batch                 # concatenated build side
    key_cols: List[Any]          # evaluated key columns (batch order)
    sorted_hashes: Any           # u64[capacity], ascending; padding = MAX
    perm: Any                    # int32[capacity]: sorted idx -> batch row
    live: Any                    # bool[capacity]
    probe: Optional[ProbeIndex] = None

    @staticmethod
    def build(batch: Batch, key_cols: List[Any],
              live: Optional[Any] = None) -> "BuildTable":
        from auron_tpu.ops.strategy import (
            join_probe_strategy, sort_strategy,
        )
        cap = batch.capacity
        h, valid = join_key_hash(key_cols, cap)
        if live is None:
            live = batch.row_mask()
        h = jnp.where(jnp.logical_and(live, valid), h, _NULL_BUILD)
        if sort_strategy(cap) == "radix":
            from auron_tpu.ops.radix_sort import stable_argsort_u64
            perm = stable_argsort_u64(h)
        else:
            from auron_tpu.ops.sort_keys import stable_argsort
            perm = stable_argsort(h)
        sorted_hashes = jnp.take(h, perm)
        probe = build_probe_index(sorted_hashes) \
            if join_probe_strategy(cap) == "partitioned" else None
        return BuildTable(batch=batch, key_cols=key_cols,
                          sorted_hashes=sorted_hashes, perm=perm,
                          live=live, probe=probe)


def probe_ranges(sorted_hashes, probe_hash, probe_valid, probe_live):
    ph = jnp.where(jnp.logical_and(probe_live, probe_valid), probe_hash,
                   _NULL_PROBE)
    lo = jnp.searchsorted(sorted_hashes, ph, side="left")
    hi = jnp.searchsorted(sorted_hashes, ph, side="right")
    counts = (hi - lo).astype(jnp.int64)
    return lo.astype(jnp.int32), counts


def _host_key_values(c: Any, idx: np.ndarray) -> List[Any]:  # jitcheck: waive (host-key verification helper: only reached via _verify_pairs_host, never on the traced all-device path)
    """Python values of column `c` at rows idx (None = null/out-of-range);
    strings normalized to bytes so host (str) and device (padded bytes)
    representations compare equal."""
    if isinstance(c, HostColumn):
        vals = c.pylist()
        out = [vals[i] if 0 <= i < len(vals) else None for i in idx]
        return [v.encode("utf-8") if isinstance(v, str) else v for v in out]
    if isinstance(c, DeviceStringColumn):
        data = np.asarray(c.data)
        lens = np.asarray(c.lengths)
        valid = np.asarray(c.validity)
        return [bytes(data[i, :lens[i]].astype(np.uint8))
                if 0 <= i < len(valid) and valid[i] else None for i in idx]
    data = np.asarray(c.data)
    valid = np.asarray(c.validity)
    return [data[i].item() if 0 <= i < len(valid) and valid[i] else None
            for i in idx]


def _verify_pairs_host(probe_keys, build_keys, probe_idx, build_idx,  # jitcheck: waive (host-key fallback: verify_pairs dispatches here only when a key column is host-resident, which the fused/jitted probe path excludes upstream)
                       pair_live):
    """Exact-equality fallback when any key column is host-resident
    (oversized strings / hybrid rows): values may live in different
    representations on the two sides, so compare as python values."""
    import jax
    pidx, bidx, live = jax.device_get([probe_idx, build_idx, pair_live])
    pidx, bidx = np.asarray(pidx), np.asarray(bidx)
    ok = np.asarray(live).copy()
    for pk, bk in zip(probe_keys, build_keys):
        pv = _host_key_values(pk, pidx)
        bv = _host_key_values(bk, bidx)
        for i in range(len(ok)):
            if ok[i] and (pv[i] is None or bv[i] is None or pv[i] != bv[i]):
                ok[i] = False
    return jnp.asarray(ok)


def verify_pairs(probe_keys: List[Any], build_keys: List[Any],
                 probe_idx, build_idx, pair_live):
    """Exact key equality for candidate pairs (hash-collision filter)."""
    if any(isinstance(c, HostColumn) for c in probe_keys + build_keys):
        return _verify_pairs_host(probe_keys, build_keys, probe_idx,
                                  build_idx, pair_live)
    ok = pair_live
    for pk, bk in zip(probe_keys, build_keys):
        p = pk.gather(probe_idx, pair_live)
        b = bk.gather(build_idx, pair_live)
        if isinstance(p, DeviceStringColumn):
            eq = S.string_eq(p, b)
        else:
            eq = p.data == b.data
        ok = jnp.logical_and(ok, jnp.logical_and(
            eq, jnp.logical_and(p.validity, b.validity)))
    return ok


def expand_pairs(lo, counts, chunk_start: int, chunk_cap: int):
    """Pair expansion for output slots [chunk_start, chunk_start+chunk_cap):
    returns (probe_idx, cand_offset, live) device vectors."""
    prefix = jnp.cumsum(counts)                      # inclusive
    starts = prefix - counts                         # exclusive prefix
    slots = chunk_start + jnp.arange(chunk_cap, dtype=jnp.int64)
    probe_idx = jnp.searchsorted(prefix, slots, side="right").astype(jnp.int32)
    total = prefix[-1] if counts.shape[0] else jnp.int64(0)
    live = slots < total
    safe_probe = jnp.clip(probe_idx, 0, counts.shape[0] - 1)
    offset = slots - jnp.take(starts, safe_probe)
    return safe_probe, offset.astype(jnp.int32), live


def null_columns_like(schema_fields, capacity: int) -> List[Any]:
    """All-null device columns for outer-join padding."""
    from auron_tpu.columnar.batch import _empty_column
    return [_empty_column(f.dtype, capacity) for f in schema_fields]


def combine_sides(out_schema: Schema, left_cols: List[Any],
                  right_cols: List[Any], num_rows: int, capacity: int,
                  extra: Optional[List[Any]] = None) -> Batch:
    cols = list(left_cols) + list(right_cols) + list(extra or [])
    return Batch(out_schema, cols, num_rows, capacity)


def _build_range_kernel():
    """Once-per-probe-batch program: key hash + build-table range lookup.
    Outputs feed every chunk of the pair kernel (so the double-searchsorted
    is never repeated per chunk)."""
    def run(pkeys, sorted_hashes, probe_num_rows):
        pcap = pkeys[0].validity.shape[0]
        plive = jnp.arange(pcap, dtype=jnp.int32) < probe_num_rows
        ph, pvalid = join_key_hash(pkeys, pcap)
        lo, counts = probe_ranges(sorted_hashes, ph, pvalid, plive)
        return lo, counts, jnp.sum(counts)
    return run


def _build_range_kernel_partitioned(b_bits: int, iters: int):
    """Partitioned-strategy range kernel: key hash + bucket dispatch +
    bounded search.  Cached per (b_bits, iters) — the static search
    depth is part of the program."""
    def run(pkeys, uvals, ustart, ucnt, bucket_start, probe_num_rows):
        pcap = pkeys[0].validity.shape[0]
        plive = jnp.arange(pcap, dtype=jnp.int32) < probe_num_rows
        ph, pvalid = join_key_hash(pkeys, pcap)
        index = ProbeIndex(uvals=uvals, ustart=ustart, ucnt=ucnt,
                           bucket_start=bucket_start, b_bits=b_bits,
                           iters=iters)
        lo, counts = probe_ranges_partitioned(index, ph, pvalid, plive)
        return lo, counts, jnp.sum(counts)
    return run


def _build_pair_kernel(emit_pairs: bool, track_build: bool,
                       side_kind: str, is_final: bool):
    """The fused per-chunk probe program: pair expansion -> verification ->
    matched-flag updates -> pair gather -> (final chunk only) probe-side
    emission gather.  Pure jax; jitted once per static-flag combination via
    kernel_cache and reused across all joins of that shape — the
    counterpart of the reference's compiled bhj/smj joiners
    (joins/bhj/full_join.rs:379)."""
    from auron_tpu.ops.base import compact_indices

    def run(probe_cols, pkeys, build_cols, bkeys, lo, counts, total, perm,
            probe_num_rows, probe_matched_in, build_matched_in, start,
            *, chunk_cap):
        pcap = probe_matched_in.shape[0]
        bcap = perm.shape[0]
        plive = jnp.arange(pcap, dtype=jnp.int32) < probe_num_rows
        probe_idx, offset, pair_live = expand_pairs(lo, counts, start,
                                                    chunk_cap)
        sorted_pos = jnp.clip(jnp.take(lo, probe_idx) + offset, 0, bcap - 1)
        build_idx = jnp.take(perm, sorted_pos)
        ok = verify_pairs(pkeys, bkeys, probe_idx, build_idx, pair_live)
        probe_matched = probe_matched_in.at[probe_idx].max(ok)
        build_matched = build_matched_in.at[build_idx].max(ok) \
            if track_build else build_matched_in
        out_p: List[Any] = []
        out_b: List[Any] = []
        n_pairs = jnp.int32(0)
        if emit_pairs:
            idx, n_pairs = compact_indices(ok, chunk_cap)
            ev = jnp.arange(chunk_cap, dtype=jnp.int32) < n_pairs
            pi = jnp.take(probe_idx, idx)
            bi = jnp.take(build_idx, idx)
            out_p = [c.gather(pi, ev) for c in probe_cols]
            out_b = [c.gather(bi, ev) for c in build_cols]
        side_cols: List[Any] = []
        n_side = jnp.int32(0)
        if is_final and side_kind in ("unmatched", "semi", "anti"):
            if side_kind == "semi":
                smask = jnp.logical_and(probe_matched, plive)
            else:
                smask = jnp.logical_and(jnp.logical_not(probe_matched),
                                        plive)
            sidx, n_side = compact_indices(smask, pcap)
            sv = jnp.arange(pcap, dtype=jnp.int32) < n_side
            side_cols = [c.gather(sidx, sv) for c in probe_cols]
        counts3 = jnp.stack([total.astype(jnp.int64),
                             n_pairs.astype(jnp.int64),
                             n_side.astype(jnp.int64)])
        return out_p, out_b, side_cols, counts3, probe_matched, build_matched
    return run

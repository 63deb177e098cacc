"""The shared join kernel: sorted-hash build table + searchsorted probe.

Build:  key columns -> u64 hash (two murmur passes packed) with null-key
        sentinels -> argsort -> (sorted_hashes, perm, build_batch)
Probe:  probe hashes -> lo/hi = searchsorted range -> candidate counts ->
        chunked pair expansion -> exact key verification -> joined batches.

All device work is eager jnp (XLA kernels); chunk sizes are fixed
capacities so shapes stay static.

One build sort (`sort_keys.stable_argsort` of the u64 hashes) and one
probe (`probe_ranges`: a double `searchsorted`) on every backend.
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
# (emit/track/side) and reused across every join of that
# shape — key/payload column structures and capacities vary per query
# by DESIGN (jax.jit's per-aval cache holds each signature's program)
jitcheck.waive_retraces(
    "join.range", 0,
    "one range kernel; key structures vary")
jitcheck.waive_retraces(
    "join.pair", 0,
    "one pair kernel per flag combination; column structures vary")

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
class BuildTable:
    """The 'hash map': build batch + hash-sorted permutation.  `live`
    marks real rows (the batch may be an UNcompacted device concat of the
    build stream — dead rows carry the null sentinel and never match)."""
    batch: Batch                 # concatenated build side
    key_cols: List[Any]          # evaluated key columns (batch order)
    sorted_hashes: Any           # u64[capacity], ascending; padding = MAX
    perm: Any                    # int32[capacity]: sorted idx -> batch row
    live: Any                    # bool[capacity]

    @staticmethod
    def build(batch: Batch, key_cols: List[Any],
              live: Optional[Any] = None) -> "BuildTable":
        from auron_tpu.ops.sort_keys import stable_argsort
        cap = batch.capacity
        h, valid = join_key_hash(key_cols, cap)
        if live is None:
            live = batch.row_mask()
        h = jnp.where(jnp.logical_and(live, valid), h, _NULL_BUILD)
        perm = stable_argsort(h)
        return BuildTable(batch=batch, key_cols=key_cols,
                          sorted_hashes=jnp.take(h, perm), perm=perm,
                          live=live)


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

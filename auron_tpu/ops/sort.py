"""External sort operator.

Analogue of sort_exec.rs:86: device in-memory sort via encoded u64 key words
+ lexsort (the key-prefix-encoding + radix-sort design, TPU-shaped), spill
of sorted runs under memory pressure, and a k-way merge of runs (loser-tree
equivalent: batch-wise safe-prefix merge on host keys) with limit/offset
pushdown (FetchLimit, auron.proto:667).
"""

from __future__ import annotations

from typing import Iterator, List, Optional, Tuple

import numpy as np
import pyarrow as pa

from auron_tpu.columnar.batch import (
    Batch, HostColumn, bucket_capacity, concat_batches,
)
from auron_tpu.exprs.compiler import build_evaluator
from auron_tpu.ir.expr import SortExpr
from auron_tpu.ir.schema import Schema
from auron_tpu.memmgr import MemConsumer, SpillManager
from auron_tpu.ops.base import (
    Operator, TaskContext, batch_size, cut_batches,
)
from auron_tpu.ops.sort_keys import (
    encode_sort_keys, lexsort_indices,
)
from auron_tpu.runtime import tracing

NUM_MAX_MERGING_BATCHES = 16  # mirror of sort_exec.rs multi-level merge cap


class SortExec(Operator, MemConsumer):
    def __init__(self, child: Operator, sort_exprs: Tuple[SortExpr, ...],
                 fetch_limit: Optional[int] = None, fetch_offset: int = 0):
        Operator.__init__(self, child.schema, [child])
        MemConsumer.__init__(self, "SortExec")
        self.sort_exprs = tuple(sort_exprs)
        self.fetch_limit = fetch_limit
        self.fetch_offset = fetch_offset
        self._key_eval = build_evaluator(
            tuple(s.child for s in self.sort_exprs), child.schema)
        self._orders = tuple((s.asc, s.nulls_first) for s in self.sort_exprs)
        self._staged: List[Batch] = []
        self._staged_bytes = 0
        self._spills = SpillManager("sort")

    # -- memory -------------------------------------------------------------

    def spill(self) -> int:
        if not self._staged:
            return 0
        freed = self._staged_bytes
        run = self._sort_staged()
        spill = self._spills.new_spill()
        size = spill.write_batches(b.to_arrow() for b in run)
        self.metrics.add("mem_spill_count", 1)
        self.metrics.add("mem_spill_size", size)
        self._staged = []
        self._staged_bytes = 0
        self.update_mem_used(0)
        return freed

    # -- sorting ------------------------------------------------------------

    def _sort_batch(self, b: Batch) -> Batch:
        # leaf spans around this operator's own steps on a batch it holds
        # (each times the host: the kernels are enqueued, nothing blocks)
        rows = b.num_rows
        with tracing.span("sort.keys", cat="op", rows=rows,
                          key_columns=len(self.sort_exprs)):
            key_cols = self._key_eval(b)
            host = any(isinstance(c, HostColumn) for c in key_cols)
            words = None if host else \
                encode_sort_keys(key_cols, self._orders)
        if host:
            with tracing.span("sort.order", cat="op", rows=rows,
                              host=True, blocked=True):
                out = self._sort_batch_host(b)
        else:
            with tracing.span("sort.order", cat="op", rows=rows):
                perm = lexsort_indices(words, rows, b.capacity)
            with tracing.span("sort.take", cat="op", rows=rows):
                out = b.gather(perm, rows)
        if self.fetch_limit is not None:
            with tracing.span("sort.cut", cat="op", rows_in=rows) as sp:
                out = out.head(self.fetch_offset + self.fetch_limit)
                sp.set_args(rows_out=out.num_rows)
        return out

    def _sort_batch_host(self, b: Batch) -> Batch:
        """Key columns living host-side (oversized strings, hybrid rows)
        can't ride the device key encoding; sort with the same numpy
        encoding the spill merger uses, so both paths order identically."""
        rb = b.to_arrow()
        words = encode_host_sort_words(self.sort_exprs, rb,
                                       self.children[0].schema)
        order = np.lexsort(tuple(reversed(words)))
        tbl = pa.Table.from_batches([rb]).take(
            pa.array(order, type=pa.int64())).combine_chunks()
        out = tbl.to_batches()
        return Batch.from_arrow(out[0] if out else rb.slice(0, 0))

    def _sort_staged(self) -> List[Batch]:
        """Sort all staged batches into one run (list of output batches)."""
        if not self._staged:
            return []
        with tracing.span("sort.run", cat="op", keys=len(self.sort_exprs),
                          batches_in=len(self._staged)) as run:
            with tracing.span("sort.concat", cat="op") as sp:
                merged = concat_batches(self.schema, self._staged)
                sp.set_args(rows=merged.num_rows)
            run.set_args(rows=merged.num_rows, capacity=merged.capacity)
            out = self._sort_batch(merged)
            with tracing.span("sort.rechunk", cat="op", rows=out.num_rows):
                return _rechunk(out, batch_size())

    # -- execution ----------------------------------------------------------

    def execute(self, ctx: TaskContext) -> Iterator[Batch]:
        try:
            with self.mem_scope(ctx):
                for b in self.child_stream(ctx):
                    if b.num_rows == 0:
                        continue
                    self._staged.append(b)
                    self._staged_bytes += b.mem_bytes()
                    self.update_mem_used(self._staged_bytes)
                if not len(self._spills):
                    out = self._sort_staged()
                    self._staged = []
                    self.update_mem_used(0)
                    yield from cut_batches(iter(out), self.fetch_offset,
                                           self.fetch_limit)
                    return
                # final in-memory run joins the spilled runs
                if self._staged:
                    self.spill()
                yield from cut_batches(
                    self._merge_spills(), self.fetch_offset,
                    self.fetch_limit)
        finally:
            self._spills.release_all()

    def _merge_spills(self) -> Iterator[Batch]:
        runs = [s.read_batches() for s in self._spills.spills]
        merger = HostKeyMerger(self.schema, self.sort_exprs)
        yield from merger.merge(runs)


def _rechunk(b: Batch, target: int) -> List[Batch]:
    if b.num_rows <= target:
        return [b]
    out = []
    arrow = b.to_arrow()
    for off in range(0, b.num_rows, target):
        out.append(Batch.from_arrow(arrow.slice(off, target)))
    return out


# ---------------------------------------------------------------------------
# host-side k-way merge of sorted runs (the loser-tree analogue): encoded
# numpy keys, safe-prefix emission
# ---------------------------------------------------------------------------

class HostKeyMerger:
    def __init__(self, schema: Schema, sort_exprs: Tuple[SortExpr, ...]):
        self.schema = schema
        self.sort_exprs = sort_exprs

    def _encode(self, rb: pa.RecordBatch) -> np.ndarray:
        """[n, n_words] uint64 matrix mirroring ops.sort_keys encoding
        (device and host agree because spilled runs were device-sorted with
        the same transform)."""
        words = encode_host_sort_words(self.sort_exprs, rb, self.schema)
        return np.stack(words, axis=1) if words \
            else np.zeros((rb.num_rows, 0), np.uint64)

    def merge(self, runs: List[Iterator[pa.RecordBatch]]) -> Iterator[Batch]:
        heads: List[Optional[pa.RecordBatch]] = []
        keys: List[Optional[np.ndarray]] = []
        iters = runs
        for it in iters:
            rb = next(it, None)
            heads.append(rb)
            keys.append(self._encode(rb) if rb is not None else None)
        pool_rb: List[pa.RecordBatch] = []
        pool_keys: List[np.ndarray] = []
        while True:
            active = [i for i, h in enumerate(heads) if h is not None]
            if not active:
                break
            # bound = min over active runs of their current batch's max key
            bound = None
            for i in active:
                mk = keys[i][-1]  # run batches are sorted: last row is max
                if bound is None or _key_lt(mk, bound):
                    bound = mk
            # move each active head into the pool, then refill heads whose
            # batch max == bound (they may have more rows <= bound next)
            for i in active:
                pool_rb.append(heads[i])
                pool_keys.append(keys[i])
                heads[i] = next(iters[i], None)
                keys[i] = self._encode(heads[i]) if heads[i] is not None \
                    else None
            all_rb = pa.Table.from_batches(pool_rb).combine_chunks()
            all_keys = np.concatenate(pool_keys, axis=0)
            order = np.lexsort(tuple(all_keys[:, j]
                                     for j in range(all_keys.shape[1] - 1,
                                                    -1, -1)))
            sorted_keys = all_keys[order]
            # safe prefix: rows <= bound, unless no run has data left.
            # This host-side searchsorted compares HOST-encoded words
            # against each other only.
            if all(h is None for h in heads):
                safe = len(order)
            else:
                safe = int(np.searchsorted(
                    _key_rank(sorted_keys), _key_rank(bound[None, :])[0],
                    side="right"))
            emit_idx = order[:safe]
            rest_idx = order[safe:]
            if safe:
                emitted = all_rb.take(pa.array(emit_idx, type=pa.int64()))
                for rb in emitted.to_batches(max_chunksize=batch_size()):
                    yield Batch.from_arrow(rb)
            if len(rest_idx):
                rest = all_rb.take(pa.array(np.sort(rest_idx),
                                            type=pa.int64()))
                pool_rb = rest.combine_chunks().to_batches()
                pool_keys = [all_keys[np.sort(rest_idx)]]
            else:
                pool_rb, pool_keys = [], []
        if pool_rb:
            all_rb = pa.Table.from_batches(pool_rb)
            all_keys = np.concatenate(pool_keys, axis=0)
            order = np.lexsort(tuple(all_keys[:, j]
                                     for j in range(all_keys.shape[1] - 1,
                                                    -1, -1)))
            emitted = all_rb.take(pa.array(order, type=pa.int64()))
            for rb in emitted.to_batches(max_chunksize=batch_size()):
                yield Batch.from_arrow(rb)


def encode_host_sort_words(sort_exprs: Tuple[SortExpr, ...],
                           rb: pa.RecordBatch,
                           schema: Schema) -> List[np.ndarray]:
    """Host mirror of ops.sort_keys.encode_sort_keys over a record batch —
    the ONE implementation both the host in-memory sort and the spill
    merger use, so their orders cannot diverge."""
    from auron_tpu.exprs.host_eval import evaluate as host_evaluate
    words: List[np.ndarray] = []
    for s in sort_exprs:
        hv = host_evaluate(s.child, rb, schema)
        words.extend(_np_encode_key(hv, s.asc, s.nulls_first))
    return words


def _key_rank(keys: np.ndarray):
    """Structured view for row-wise lexicographic searchsorted."""
    n_words = keys.shape[1]
    dt = np.dtype([(f"w{j}", np.uint64) for j in range(n_words)])
    return np.ascontiguousarray(keys).view(dt).reshape(-1)


def _key_lt(a: np.ndarray, b: np.ndarray) -> bool:
    for x, y in zip(a, b):
        if x != y:
            return bool(x < y)
    return False


def _np_encode_key(hv, asc: bool, nulls_first: bool) -> List[np.ndarray]:
    """numpy mirror of ops.sort_keys.encode_key_column over a host value."""
    from auron_tpu.ir.schema import TypeId
    n = len(hv.vals)
    words: List[np.ndarray] = []
    dt = hv.dtype
    if dt.is_stringlike:
        # FIXED width across the whole merge so every batch yields the same
        # word count (keys beyond this width tie-break by length — same
        # clamp the device representation has)
        from auron_tpu.config import conf
        w_pad = ((int(conf.get("auron.string.device.max.width")) + 7) // 8) * 8
        bs = [(v if isinstance(v, bytes) else str(v).encode("utf-8"))[:w_pad]
              if m else b"" for v, m in zip(hv.vals, hv.mask)]
        mat = np.zeros((n, w_pad), np.uint8)
        for i, b in enumerate(bs):
            mat[i, :len(b)] = np.frombuffer(b, np.uint8)
        for blk in range(0, w_pad, 8):
            word = np.zeros(n, np.uint64)
            for j in range(8):
                word = (word << np.uint64(8)) | mat[:, blk + j].astype(np.uint64)
            words.append(word)
        words.append(np.array([len(b) for b in bs], np.uint64))
    elif dt.id == TypeId.FLOAT64:
        bits = hv.vals.astype(np.float64).view(np.uint64)
        neg = (bits & np.uint64(1 << 63)) != 0
        words = [np.where(neg, ~bits, bits ^ np.uint64(1 << 63))]
    elif dt.id == TypeId.FLOAT32:
        # MUST mirror the device encoding (_orderable_u64_from_f32: f32
        # bits in the HIGH u32 word) — these host words are compared
        # against device-encoded row words (range bounds, merges); the
        # former f64-widened encoding lived in a different key space and
        # made every f32 row-vs-bound comparison meaningless
        bits = hv.vals.astype(np.float32).view(np.uint32) \
            .astype(np.uint64) << np.uint64(32)
        neg = (bits & np.uint64(1 << 63)) != 0
        words = [np.where(neg, ~bits, bits ^ np.uint64(1 << 63))
                 & np.uint64(0xFFFFFFFF00000000)]
    elif dt.id == TypeId.BOOL:
        words = [hv.vals.astype(np.uint32)]
    elif dt.id == TypeId.DECIMAL:
        # hv.vals already hold the UNSCALED integer (arrow_to_hv).
        # p<=18: one u64 word, bit-identical to the device encoding so
        # device-sorted runs and host merges/bounds stay aligned;
        # p>18 (host-resident): 128-bit two's complement as two words
        # (|unscaled| < 10^38 < 2^127, so no wrap).
        his = np.zeros(n, np.uint64)
        los = np.zeros(n, np.uint64)
        for i, (v, m) in enumerate(zip(hv.vals, hv.mask)):
            if not m or v is None:
                continue
            u = int(v) & ((1 << 128) - 1)
            his[i] = u >> 64
            los[i] = u & ((1 << 64) - 1)
        if not dt.is_wide_decimal:
            words = [los ^ np.uint64(1 << 63)]
        else:
            words = [his ^ np.uint64(1 << 63), los]
    elif dt.id in (TypeId.INT8, TypeId.INT16, TypeId.INT32,
                   TypeId.DATE32):
        # u32 mirror of the device narrow-int encoding (sort_keys.py):
        # same VALUES, so device-sorted runs, host merges, and range
        # bounds all promote consistently
        words = [hv.vals.astype(np.int32).view(np.uint32)
                 ^ np.uint32(1 << 31)]
    else:
        words = [hv.vals.astype(np.int64).view(np.uint64)
                 ^ np.uint64(1 << 63)]
    if not asc:
        words = [~w for w in words]
    null_rank = np.where(hv.mask,
                         np.uint64(1) if nulls_first else np.uint64(0),
                         np.uint64(0) if nulls_first else np.uint64(1))
    return [null_rank] + words

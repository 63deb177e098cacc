"""IPC reader/writer + FFI reader.

Analogues of ipc_reader_exec.rs:65 (reads compressed-IPC blocks from
JVM-provided channels — here from the resource registry: bytes, a list of
byte blocks, or a file path), ipc_writer_exec.rs:43 (broadcast collect
path), and ffi_reader_exec.rs:46 (imports front-end Arrow batches through
the Arrow C-Data interface / any python RecordBatch iterable).
"""

from __future__ import annotations

import io
import os
from typing import Any, Iterator

import pyarrow as pa

from auron_tpu.columnar import serde as batch_serde
from auron_tpu.columnar.batch import Batch
from auron_tpu.config import conf
from auron_tpu.ir.schema import Schema
from auron_tpu.ops.base import Operator, TaskContext
from auron_tpu.runtime import tracing


class IpcReaderExec(Operator):
    def __init__(self, schema: Schema, resource_id: str):
        super().__init__(schema, [])
        self.resource_id = resource_id

    def execute(self, ctx: TaskContext) -> Iterator[Batch]:
        src = ctx.resources.get(self.resource_id)
        fetched_from_shuffle = hasattr(src, "for_partition")
        if fetched_from_shuffle:
            # partition-indexed source (shuffle reduce side): pick this
            # task's block list (the per-task segment-iterator contract of
            # AuronBlockStoreShuffleReader.readBlocks)
            src = src.for_partition(ctx.partition_id)
            nbytes = sum(len(b) for b in _flat_blocks(src))
            if nbytes:
                from auron_tpu.runtime import counters
                counters.bump("shuffle_bytes_fetched", nbytes)
                self.metrics.add("shuffle_read_bytes", nbytes)
        import time
        t0 = time.perf_counter_ns()
        n = 0
        for item in _iter_ipc(src):
            if isinstance(item, Batch):
                # v2 frame: already the device representation — rename
                # to this reader's declared schema, no arrow decode
                n += item.num_rows
                yield item if item.schema == self.schema else \
                    Batch(self.schema, item.columns, item.num_rows_raw,
                          item.capacity)
            else:
                n += item.num_rows
                yield Batch.from_arrow(item, schema=self.schema)
        self.metrics.add("shuffle_read_rows", n)
        self.metrics.add("shuffle_read_time_ns", time.perf_counter_ns() - t0)


def _flat_blocks(src) -> list:
    """Flatten nested block lists to leaf byte blocks."""
    if isinstance(src, (bytes, bytearray, memoryview)):
        return [src]
    if isinstance(src, (list, tuple)):
        out = []
        for b in src:
            out.extend(_flat_blocks(b))
        return out
    return []


class _ChainedBlocks:
    """File-like over a sequence of byte blocks: the reduce side of one
    exchange reads the CONCATENATION of a map stream's pushed chunks
    (v2 emits its schema header once per stream, so chunks after the
    first are frame-only and cannot be parsed block-by-block)."""

    __slots__ = ("_blocks", "_i", "_off")

    def __init__(self, blocks) -> None:
        self._blocks = [memoryview(b) for b in blocks if len(b)]
        self._i = 0
        self._off = 0

    def read(self, n: int = -1) -> bytes:
        if n is None or n < 0:
            parts = [self._blocks[self._i][self._off:]]
            parts += self._blocks[self._i + 1:]
            self._i, self._off = len(self._blocks), 0
            return b"".join(parts)
        out = bytearray()
        while n > 0 and self._i < len(self._blocks):
            blk = self._blocks[self._i]
            take = blk[self._off:self._off + n]
            out += take
            n -= len(take)
            self._off += len(take)
            if self._off >= len(blk):
                self._i += 1
                self._off = 0
        return bytes(out)


def _iter_ipc(src) -> Iterator[Any]:
    """Frames from any IPC source: pa.RecordBatch (v1) or device Batch
    (v2), via columnar.serde.read_batches."""
    if isinstance(src, (bytes, bytearray, memoryview)):
        yield from batch_serde.read_batches(io.BytesIO(bytes(src)))
    elif isinstance(src, str) and os.path.exists(src):
        with open(src, "rb") as f:
            yield from batch_serde.read_batches(f)
    elif hasattr(src, "read"):
        yield from batch_serde.read_batches(src)
    elif isinstance(src, (list, tuple)):
        yield from batch_serde.read_batches(
            _ChainedBlocks(_flat_blocks(src)))
    else:
        raise TypeError(f"unsupported IPC source {type(src)}")


class IpcWriterExec(Operator):
    """Serializes child output as compressed IPC into the resource registry
    under `resource_id` (the broadcast collect path:
    NativeBroadcastExchangeBase.collectNative)."""

    def __init__(self, child: Operator, resource_id: str):
        super().__init__(child.schema, [child])
        self.resource_id = resource_id

    def execute(self, ctx: TaskContext) -> Iterator[Batch]:
        sink = io.BytesIO()
        rows = 0
        for b in self.child_stream(ctx):
            if b.num_rows:
                batch_serde.write_one_batch(b.to_arrow(), sink)
                rows += b.num_rows
        ctx.resources.put(self.resource_id, sink.getvalue())
        self.metrics.add("shuffle_write_rows", rows)
        return
        yield  # generator


class FFIReaderExec(Operator):
    """Imports batches produced by a front-end: the resource may be a
    pyarrow RecordBatchReader, an iterable of RecordBatches, a Table, or a
    pair of Arrow C-Data capsules.

    Decoded device batches are cached per RecordBatch identity (weak,
    byte-budgeted by `auron.ffi.ingest.cache.mb`): repeated executes over
    one materialized source — warm runs, multi-partition broadcast
    rebuilds — re-upload nothing, the serial-path sibling of the SPMD
    source shard cache ("batches stay on device across the fragment")."""

    def __init__(self, schema: Schema, resource_id: str):
        super().__init__(schema, [])
        self.resource_id = resource_id

    def execute(self, ctx: TaskContext) -> Iterator[Batch]:
        src = ctx.resources.get(self.resource_id)
        budget_mb = int(conf.get("auron.ffi.ingest.cache.mb"))
        for rb in _iter_arrow(src):
            # host -> device, or the ingest cache's hit in its place
            with tracing.span("ffi.to_device", cat="scan") as sp:
                b, cached = self._to_device(rb, budget_mb)
                if sp.armed:
                    sp.set_args(rows=rb.num_rows, bytes=rb.nbytes,
                                cached=int(cached))
            yield b

    def _to_device(self, rb, budget_mb: int) -> "tuple[Batch, bool]":
        if budget_mb <= 0 or not isinstance(rb, pa.RecordBatch):
            return Batch.from_arrow(rb, schema=self.schema), False
        hit = _ingest_cache_get(rb)
        if hit is not None and hit.schema == self.schema:
            self.metrics.add("ffi_ingest_cache_hits", 1)
            return hit, True
        b = Batch.from_arrow(rb, schema=self.schema)
        _ingest_cache_put(rb, b, budget_mb)
        return b, False


# RecordBatch identity (id()) -> (weakref to the source, decoded Batch,
# size).  pyarrow RecordBatches are weakref-able but not hashable, so
# the dict keys by id with the weakref guarding against id reuse; a FIFO
# byte budget bounds what pinned sources can hold in device memory.
import weakref as _weakref

_INGEST_CACHE: dict = {}
_INGEST_ORDER: list = []     # ids in insertion order
_INGEST_BYTES = [0]


def _ingest_cache_get(rb) -> "Batch | None":
    entry = _INGEST_CACHE.get(id(rb))
    if entry is None or entry[0]() is not rb:
        return None
    return entry[1]


def _ingest_cache_put(rb, batch: Batch, budget_mb: int) -> None:
    size = batch.mem_bytes()
    if size > budget_mb << 20:
        return
    try:
        ref = _weakref.ref(rb, lambda _r, _i=id(rb):
                           _ingest_cache_drop(_i))
    except TypeError:
        return
    _INGEST_CACHE[id(rb)] = (ref, batch, size)
    _INGEST_ORDER.append(id(rb))
    _INGEST_BYTES[0] += size
    while _INGEST_BYTES[0] > budget_mb << 20 and _INGEST_ORDER:
        _ingest_cache_drop(_INGEST_ORDER.pop(0))


def ingest_cache_info() -> dict:
    """Observability hook for the profiling server's /metrics view:
    resident decoded-source entries and device bytes held."""
    return {"entries": len(_INGEST_CACHE), "bytes": _INGEST_BYTES[0]}


def _ingest_cache_drop(key: int) -> None:
    entry = _INGEST_CACHE.pop(key, None)
    if entry is not None:
        _INGEST_BYTES[0] -= entry[2]


def _iter_arrow(src) -> Iterator[pa.RecordBatch]:
    if isinstance(src, pa.RecordBatch):
        yield src
    elif isinstance(src, pa.Table):
        yield from src.to_batches()
    elif isinstance(src, pa.RecordBatchReader):
        for rb in src:
            yield rb
    elif isinstance(src, tuple) and len(src) == 2:
        # Arrow C-Data (array_capsule, schema_capsule) from a foreign runtime
        rb = pa.RecordBatch._import_from_c_capsule(*src)
        yield rb
    elif callable(src):
        yield from _iter_arrow(src())
    else:
        for rb in src:
            if isinstance(rb, pa.RecordBatch):
                yield rb
            else:
                yield from _iter_arrow(rb)

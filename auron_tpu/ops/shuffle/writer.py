"""Shuffle writers.

ShuffleWriterExec (shuffle_writer_exec.rs:51 + sort_repartitioner.rs +
buffered_data.rs): computes partition ids on device, radix-groups rows by
id (argsort), serializes per-partition compressed IPC runs into one data
file plus an offset index file — the reference's exact on-disk layout
(data + int64 offsets), so a Spark-side reader could fetch ranges.

RssShuffleWriterExec (rss_shuffle_writer_exec.rs:52 + shuffle/rss.rs): same
partitioning, but pushes per-partition buffers to a pluggable
RssPartitionWriter (the Celeborn/Uniffle SPI analogue) registered in the
resource registry.
"""

from __future__ import annotations

import io
import os
import struct
from typing import Any, Dict, Iterator, List, Optional

import jax.numpy as jnp
import numpy as np

from auron_tpu.columnar import serde as batch_serde
from auron_tpu.runtime import lockcheck
from auron_tpu.columnar.batch import Batch, bucket_capacity
from auron_tpu.native import bindings
from auron_tpu.ir.plan import Partitioning
from auron_tpu.ir.schema import DataType, Field, Schema
from auron_tpu.memmgr import MemConsumer, SpillManager
from auron_tpu.ops.base import Operator, TaskContext
from auron_tpu.ops.shuffle.partitioner import PartitionIdComputer


class RssPartitionWriter:
    """SPI the native writer pushes partition bytes into
    (RssPartitionWriterBase.scala:21 analogue).  Implementations: local
    files, in-memory service, Celeborn/Uniffle-style clients.

    `transport` drives the exchange codec policy (columnar.serde
    .exchange_codec): "local" writers keep the bytes in-process (no
    compression by default), everything else is wire-bound."""

    transport = "remote"

    def write(self, partition_id: int, data: bytes) -> None:
        raise NotImplementedError

    def flush(self) -> None:
        pass


class _PartitionBuffers(MemConsumer):
    """Staged per-partition rows (BufferedData analogue) with spill to
    per-partition compressed runs.  With wire format v2
    (auron.serde.format.version) frames carry the raw device layout and
    each partition's stream opens with one schema header."""

    def __init__(self, n: int, schema: Schema):
        super().__init__("ShuffleWriter")
        self.n = n
        self.schema = schema
        self.v2 = batch_serde.format_version() >= 2
        self._header = batch_serde.encode_stream_header(schema) \
            if self.v2 else b""
        self.runs: List[Dict[int, bytes]] = []   # spilled run: pid -> frames
        self.staged: Dict[int, List[Batch]] = {}
        self.staged_bytes = 0

    def add(self, pid: int, b: Batch) -> None:
        self.staged.setdefault(pid, []).append(b)
        self.staged_bytes += b.mem_bytes()
        self.update_mem_used(self.staged_bytes)

    def _frame(self, b: Batch, sink) -> None:
        if self.v2:
            batch_serde.encode_batch_v2(b, out=sink)
        else:
            batch_serde.write_one_batch(b.to_arrow(), sink)

    def spill(self) -> int:
        if not self.staged:
            return 0
        freed = self.staged_bytes
        run: Dict[int, bytes] = {}
        for pid, batches in sorted(self.staged.items()):
            sink = io.BytesIO()
            for b in batches:
                self._frame(b, sink)
            run[pid] = sink.getvalue()
        self.runs.append(run)
        self.staged = {}
        self.staged_bytes = 0
        self.update_mem_used(0)
        return freed

    def partition_bytes(self, pid: int) -> bytes:
        """All frames for a partition (spilled runs + staged), concatenated
        — frames are self-delimiting so concatenation is valid.  A v2
        partition stream opens with the schema header (once)."""
        out = io.BytesIO()
        for run in self.runs:
            if pid in run:
                if self.v2 and not out.tell():
                    out.write(self._header)
                out.write(run[pid])
        for b in self.staged.get(pid, []):
            if self.v2 and not out.tell():
                out.write(self._header)
            self._frame(b, out)
        return out.getvalue()


class _ShuffleWriterBase(Operator):
    def __init__(self, child: Operator, partitioning: Partitioning,
                 name: str):
        out_schema = Schema((Field("partition", DataType.int32()),
                             Field("bytes", DataType.int64()),
                             Field("rows", DataType.int64())))
        Operator.__init__(self, out_schema, [child], name=name)
        self.partitioning = partitioning
        self.child_schema = child.schema
        self._computer = PartitionIdComputer(partitioning, child.schema)
        # pid fusion (auron.shuffle.pid.fuse.enable): when the child is
        # a fused fragment with device-capable keys, splice the pid
        # computation into its program — batches arrive with one extra
        # PID_FIELD column instead of paying a standalone computer
        # dispatch over the materialized fragment output
        self._pid_fused = False
        from auron_tpu.config import conf
        if partitioning.num_partitions > 1 and \
                bool(conf.get("auron.shuffle.pid.fuse.enable")):
            from auron_tpu.ops.fused import FusedFragmentExec
            if isinstance(child, FusedFragmentExec):
                self._pid_fused = child.enable_pid_fusion(partitioning)

    def _partitioned_stream(self, ctx: TaskContext):
        """Yields (pid, sub_batch) pairs per input batch.

        Grouping strategy (reference buffered_data.rs:285 radix sort): pull
        the partition-id vector to host once per batch, run the C++ counting
        sort (native/host_runtime.cpp auron_partition_sort; numpy fallback),
        then issue exactly one device gather per non-empty partition with a
        right-sized index buffer — instead of one full-capacity mask
        compaction per *declared* partition.
        """
        import time

        from auron_tpu.ops.fused import PID_FIELD

        row_start = 0
        n = self.partitioning.num_partitions
        for b in self.child_stream(ctx):
            if b.num_rows == 0:
                continue
            t0 = time.perf_counter_ns()
            pids = None
            if self._pid_fused and b.schema.fields and \
                    b.schema.fields[-1].name == PID_FIELD:
                # the producing fragment already computed the ids in
                # ITS program — pop the column, no extra dispatch
                pids = b.columns[-1].data
                b = Batch(b.schema.select(range(len(b.schema) - 1)),
                          b.columns[:-1], b.num_rows_raw, b.capacity)
                self.metrics.add("pid_fused_batches", 1)
            if pids is None:
                pids = self._computer(b, partition_id=ctx.partition_id,
                                      row_start=row_start)
            row_start += b.num_rows
            # the documented once-per-batch pid fetch, through the
            # sanctioned channel (np.asarray on the device vector was
            # an IMPLICIT transfer: uncounted, and a diagnostic under
            # the jitcheck transfer guard on accelerator backends)
            from auron_tpu.ops.kernel_cache import host_sync
            host_pids = np.asarray(
                host_sync(pids))[:b.num_rows].astype(np.int32)
            perm, offsets = bindings.partition_sort(host_pids, n)
            for pid in range(n):
                lo, hi = int(offsets[pid]), int(offsets[pid + 1])
                if hi == lo:
                    continue
                c = hi - lo
                cap = bucket_capacity(c)
                idx = np.zeros(cap, dtype=np.int64)
                idx[:c] = perm[lo:hi]
                yield pid, b.gather(jnp.asarray(idx), c)
            self.metrics.add("shuffle_write_time_ns",
                             time.perf_counter_ns() - t0)
            self.metrics.add("shuffle_write_rows", b.num_rows)


class ShuffleWriterExec(_ShuffleWriterBase):
    def __init__(self, child: Operator, partitioning: Partitioning,
                 output_data_file: str, output_index_file: str):
        super().__init__(child, partitioning, "ShuffleWriterExec")
        self.output_data_file = output_data_file
        self.output_index_file = output_index_file

    def execute(self, ctx: TaskContext) -> Iterator[Batch]:
        bufs = _PartitionBuffers(self.partitioning.num_partitions,
                                 self.children[0].schema)
        rows_per_pid: Dict[int, int] = {}
        with self.mem_scope(ctx, consumer=bufs):
            for pid, sub in self._partitioned_stream(ctx):
                bufs.add(pid, sub)
                rows_per_pid[pid] = rows_per_pid.get(pid, 0) + sub.num_rows
            n = self.partitioning.num_partitions
            offsets = [0] * (n + 1)
            with open(self.output_data_file, "wb") as f:
                for pid in range(n):
                    data = bufs.partition_bytes(pid)
                    f.write(data)
                    offsets[pid + 1] = offsets[pid] + len(data)
            with open(self.output_index_file, "wb") as f:
                f.write(struct.pack(f"<{n + 1}q", *offsets))
            lengths = [offsets[i + 1] - offsets[i] for i in range(n)]
            out_rows = [{"partition": pid, "bytes": lengths[pid],
                         "rows": rows_per_pid.get(pid, 0)}
                        for pid in range(n)]
            import pyarrow as pa
            from auron_tpu.ir.schema import to_arrow_schema
            yield Batch.from_arrow(pa.Table.from_pylist(
                out_rows, schema=to_arrow_schema(self.schema))
                .combine_chunks().to_batches()[0])


class RssShuffleWriterExec(_ShuffleWriterBase):
    def __init__(self, child: Operator, partitioning: Partitioning,
                 rss_resource_id: str):
        super().__init__(child, partitioning, "RssShuffleWriterExec")
        self.rss_resource_id = rss_resource_id

    def execute(self, ctx: TaskContext) -> Iterator[Batch]:
        from auron_tpu.runtime import counters
        writer: RssPartitionWriter = ctx.resources.get(self.rss_resource_id)
        rows_per_pid: Dict[int, int] = {}
        bytes_per_pid: Dict[int, int] = {}
        v2 = batch_serde.format_version() >= 2
        header = batch_serde.encode_stream_header(self.child_schema) \
            if v2 else b""
        # per-transport codec policy: in-process pushes skip the
        # compress-only-to-decompress round trip (codec.local=none)
        codec = batch_serde.exchange_codec(
            getattr(writer, "transport", "remote"))
        started: set = set()
        for pid, sub in self._partitioned_stream(ctx):
            if v2:
                # schema once per (map, partition) stream, then raw
                # device-layout frames — no arrow materialization
                frame = batch_serde.encode_batch_v2(sub, codec=codec)
                data = frame if pid in started else header + frame
                started.add(pid)
            else:
                sink = io.BytesIO()
                batch_serde.write_one_batch(sub.to_arrow(), sink,
                                            codec=codec)
                data = sink.getvalue()
            writer.write(pid, data)
            counters.bump("shuffle_bytes_pushed", len(data))
            self.metrics.add("shuffle_write_bytes", len(data))
            rows_per_pid[pid] = rows_per_pid.get(pid, 0) + sub.num_rows
            bytes_per_pid[pid] = bytes_per_pid.get(pid, 0) + len(data)
        writer.flush()
        out_rows = [{"partition": pid, "bytes": bytes_per_pid.get(pid, 0),
                     "rows": rows_per_pid.get(pid, 0)}
                    for pid in range(self.partitioning.num_partitions)]
        import pyarrow as pa
        from auron_tpu.ir.schema import to_arrow_schema
        yield Batch.from_arrow(pa.Table.from_pylist(
            out_rows, schema=to_arrow_schema(self.schema))
            .combine_chunks().to_batches()[0])


class InProcessShuffleService:
    """Single-host multi-stage exchange: map tasks write partition frames
    here; reduce tasks read them back via IpcReaderExec resources.  The
    analogue of the Spark block-store path (AuronShuffleManager) for the
    standalone driver."""

    def __init__(self) -> None:
        # (shuffle_id, reduce_pid) -> [(map_id, block)]; map tasks now run
        # on a thread pool, so reads sort by map id to keep reduce-side
        # block order deterministic (differential tests compare per-
        # partition streams)
        self._blocks: Dict[tuple, List[tuple]] = {}
        self._lock = lockcheck.Lock("shuffle.inproc")

    def rss_writer(self, shuffle_id: str, map_id: int) -> RssPartitionWriter:
        svc = self

        class _W(RssPartitionWriter):
            """Stages locally, commits atomically in flush(): a map task
            replayed by the retry tier (runtime/retry.py) re-creates its
            writer and the commit REPLACES any blocks an earlier partial
            attempt left behind — the in-process counterpart of the
            remote services' push_id/block_id dedup.  Each push/commit is
            itself retried like the remote clients retry their push RPCs
            (the fault point raises BEFORE any mutation, so a replayed
            push never double-stages)."""

            transport = "local"

            def __init__(self) -> None:
                self._staged: Dict[int, List[bytes]] = {}

            def _push(self, partition_id: int, data: bytes) -> None:
                from auron_tpu.faults import fault_point
                fault_point("shuffle.push")
                self._staged.setdefault(partition_id, []).append(data)

            def _commit(self) -> None:
                from auron_tpu.faults import fault_point
                fault_point("shuffle.push")
                with svc._lock:
                    for pid, frames in self._staged.items():
                        blocks = svc._blocks.setdefault(
                            (shuffle_id, pid), [])
                        blocks[:] = [e for e in blocks if e[0] != map_id]
                        blocks.extend((map_id, d) for d in frames)
                self._staged = {}

            def write(self, partition_id: int, data: bytes) -> None:
                from auron_tpu.runtime.retry import (
                    RetryPolicy, call_with_retry,
                )
                from auron_tpu.runtime.tracing import span
                with span("shuffle.push", cat="shuffle",
                          partition=partition_id, nbytes=len(data)):
                    call_with_retry(
                        lambda: self._push(partition_id, data),
                        policy=RetryPolicy.from_conf(),
                        label="in-process shuffle push")

            def flush(self) -> None:
                from auron_tpu.runtime.retry import (
                    RetryPolicy, call_with_retry,
                )
                call_with_retry(self._commit,
                                policy=RetryPolicy.from_conf(),
                                label="in-process shuffle commit")
        return _W()

    def reduce_blocks(self, shuffle_id: str, reduce_pid: int) -> List[bytes]:
        from auron_tpu.faults import fault_point
        fault_point("shuffle.fetch")
        with self._lock:
            entries = list(self._blocks.get((shuffle_id, reduce_pid), []))
        return [d for _mid, d in sorted(entries, key=lambda e: e[0])]

    def clear(self, shuffle_id: str) -> None:
        with self._lock:
            for k in [k for k in self._blocks if k[0] == shuffle_id]:
                del self._blocks[k]

"""Parquet scan.

Analogue of parquet_exec.rs:70: file-group driven scan with predicate
pushdown (row-group statistics + bloom filters via pyarrow), column
projection, and hive-partition column injection.  Host IO decodes Arrow
batches (pyarrow's parquet reader is the InternalFileReader analogue); the
prefetch thread pool overlaps IO with device compute.
"""

from __future__ import annotations

import concurrent.futures as cf
from typing import Any, Iterator, List, Optional, Tuple

import pyarrow as pa
import pyarrow.dataset as pads
import pyarrow.parquet as pq

from auron_tpu.columnar.batch import Batch
from auron_tpu.config import conf
from auron_tpu.ir.plan import FileGroup
from auron_tpu.ir.schema import Schema, to_arrow_schema
from auron_tpu.ops.base import Operator, TaskContext, batch_size
from auron_tpu.ops.scan.pushdown import expr_to_arrow_filter
from auron_tpu.runtime import tracing


def _open_for_read(path: str):
    """Local paths go straight to pyarrow; scheme-qualified paths
    (gs://, hdfs://, memory://, ...) resolve through the FS bridge
    (formats/fs.py — the hadoop_fs.rs Fs/FsProvider analogue)."""
    from auron_tpu.formats import fs
    if fs.is_remote(path):
        return fs.open_input(path)
    return path


class ParquetScanExec(Operator):
    def __init__(self, schema: Schema, file_groups: Tuple[FileGroup, ...],
                 projection: Tuple[int, ...] = (), predicate=None,
                 partition_schema: Optional[Schema] = None,
                 partition_values: Tuple[Tuple[Any, ...], ...] = ()):
        proj = tuple(projection) or tuple(range(len(schema)))
        out_schema = schema.select(proj)
        if partition_schema:
            out_schema = out_schema.concat(partition_schema)
        super().__init__(out_schema, [])
        self.file_schema = schema
        self.file_groups = tuple(file_groups)
        self.projection = proj
        self.predicate = predicate
        self.partition_schema = partition_schema
        self.partition_values = tuple(partition_values)

    def _files_for(self, ctx: TaskContext) -> Optional[Tuple[FileGroup, Tuple]]:
        gi = ctx.partition_id
        if gi >= len(self.file_groups):
            return None  # extra partitions are empty, never duplicated
        pv = self.partition_values[gi] if gi < len(self.partition_values) \
            else ()
        return self.file_groups[gi], pv

    def execute(self, ctx: TaskContext) -> Iterator[Batch]:
        return self._read(ctx, Batch.from_arrow)

    def execute_arrow(self, ctx: TaskContext) -> Iterator[pa.RecordBatch]:
        return self._read(ctx, None)

    def _read(self, ctx: TaskContext, to_device) -> Iterator[Any]:
        """The read loop: record batches patched to the plan's schema,
        each handed on as `to_device` makes it, or as it is."""
        if not self.file_groups:
            return
        found = self._files_for(ctx)
        if found is None:
            return
        group, pvals = found
        names = [self.file_schema[i].name for i in self.projection]
        out_schema = to_arrow_schema(self.schema)
        filt = None
        if self.predicate is not None and \
                conf.get("auron.parquet.enable.page.filtering"):
            filt = expr_to_arrow_filter(self.predicate, self.file_schema)
        from auron_tpu.faults import fault_point
        for path in group.paths:
            # injectable site OUTSIDE the corrupted-file catch: an
            # injected io fault must reach the retry tier (task replay),
            # never be swallowed as a skipped "corrupted" file — that
            # would silently change results under chaos
            fault_point("scan.parquet.open")
            try:
                pf = pq.ParquetFile(_open_for_read(path))
            except Exception:
                if conf.get("auron.ignore.corrupted.files"):
                    continue
                raise
            fault_point("scan.parquet.read")
            row_groups = self._prune_row_groups(pf, filt)
            self.metrics.add("parquet_row_groups_pruned",
                             pf.num_row_groups - len(row_groups))
            self.metrics.add("parquet_row_groups_read", len(row_groups))
            if not row_groups:
                continue
            avail = set(pf.schema_arrow.names)
            cols = [n for n in names if n in avail]
            pulls = pf.iter_batches(batch_size=batch_size(),
                                    row_groups=row_groups, columns=cols)
            while True:
                # parquet -> Arrow; the last pull finds the end
                with tracing.span("scan.decode", cat="scan") as sp:
                    rb = next(pulls, None)
                    if rb is not None and sp.armed:
                        sp.set_args(rows=rb.num_rows, bytes=rb.nbytes)
                if rb is None:
                    break
                with tracing.span("scan.to_device", cat="scan"):
                    out = self._patch(rb, names, pvals, out_schema)
                    if to_device is not None:
                        out = to_device(out)
                yield out

    def _prune_row_groups(self, pf: pq.ParquetFile, filt) -> List[int]:
        from auron_tpu.ops.scan.pushdown import prune_parquet_row_groups
        return prune_parquet_row_groups(
            pf, filt, use_bloom=bool(conf.get("auron.parquet.enable.bloom.filter")))

    def _patch(self, rb: pa.RecordBatch, names, pvals,
               schema: pa.Schema) -> pa.RecordBatch:
        # re-order/patch missing columns (schema evolution: absent -> null)
        at = {n: i for i, n in enumerate(rb.schema.names)}
        arrays = [rb.column(at[n]) if n in at else
                  pa.nulls(rb.num_rows, type=schema.field(j).type)
                  for j, n in enumerate(names)]
        for j, (_f, v) in enumerate(zip(self.partition_schema or (), pvals),
                                    len(names)):
            arrays.append(pa.array([v] * rb.num_rows,
                                   type=schema.field(j).type))
        return pa.RecordBatch.from_arrays(arrays, schema=schema)


class ParquetSinkExec(Operator):
    """Native parquet write incl. dynamic partitions
    (parquet_sink_exec.rs:55 / NativeParquetSinkUtils)."""

    def __init__(self, child: Operator, output_dir: str,
                 partition_cols: Tuple[str, ...] = (),
                 compression: str = "zstd", props=()):
        from auron_tpu.ir.schema import DataType, Field
        super().__init__(Schema((Field("path", DataType.string()),
                                 Field("rows", DataType.int64()))), [child])
        self.output_dir = output_dir
        self.partition_cols = tuple(partition_cols)
        self.compression = compression
        self.props = dict(props)

    def execute(self, ctx: TaskContext) -> Iterator[Batch]:
        import os
        import pyarrow.parquet as pqm
        from auron_tpu.formats import fs as FS
        FS.makedirs(self.output_dir)
        child_schema = self.children[0].schema
        writers = {}
        counts = {}
        try:
            for b in self.child_stream(ctx):
                if b.num_rows == 0:
                    continue
                rb = b.to_arrow()
                for key, part in self._split_partitions(rb):
                    w = writers.get(key)
                    if w is None:
                        d = os.path.join(self.output_dir, *key)
                        FS.makedirs(d)
                        path = os.path.join(
                            d, f"part-{ctx.partition_id:05d}.parquet")
                        sink = FS.open_output(path) if FS.is_remote(path) \
                            else path
                        w = pqm.ParquetWriter(sink, part.schema,
                                              compression=self.compression)
                        writers[key] = (w, path)
                        counts[key] = 0
                    writers[key][0].write_batch(part)
                    counts[key] += part.num_rows
        finally:
            for w, _ in writers.values():
                w.close()
        rows = [{"path": path, "rows": counts[key]}
                for key, (w, path) in writers.items()]
        if rows:
            yield Batch.from_arrow(pa.Table.from_pylist(
                rows, schema=to_arrow_schema(self.schema))
                .combine_chunks().to_batches()[0])

    def _split_partitions(self, rb: pa.RecordBatch):
        yield from split_dynamic_partitions(rb, self.partition_cols)


def split_dynamic_partitions(rb: pa.RecordBatch, partition_cols):
    """Split a batch by dynamic-partition column values -> (dir_key_tuple,
    sub_batch without partition cols); shared by the parquet and orc sinks
    (Native{Parquet,Orc}SinkUtils analogue)."""
    if not partition_cols:
        yield (), rb
        return
    import pyarrow.compute as pc
    tbl = pa.Table.from_batches([rb])
    keys = [tbl.column(c) for c in partition_cols]
    rest = tbl.drop_columns(list(partition_cols))
    combos = set(zip(*[k.to_pylist() for k in keys]))
    for combo in combos:
        mask = None
        for c, v in zip(partition_cols, combo):
            m = pc.is_null(tbl.column(c)) if v is None else \
                pc.equal(tbl.column(c), pa.scalar(v))
            mask = m if mask is None else pc.and_(mask, m)
        part = rest.filter(mask).combine_chunks()
        dirkey = tuple(f"{c}={'__HIVE_DEFAULT_PARTITION__' if v is None else v}"
                       for c, v in zip(partition_cols, combo))
        for batch in part.to_batches():
            yield dirkey, batch

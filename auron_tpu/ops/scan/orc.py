"""ORC scan + sink (analogue of orc_exec.rs:68 / orc_sink_exec.rs:54).

Host IO via pyarrow.orc; supports positional schema evolution
(FORCE_POSITIONAL_EVOLUTION: match file columns by ordinal instead of name)
and case-insensitive name matching like the reference's evolution flags.
"""

from __future__ import annotations

from typing import Any, Iterator, Optional, Tuple

import pyarrow as pa

from auron_tpu.columnar.batch import Batch
from auron_tpu.config import conf
from auron_tpu.ir.plan import FileGroup
from auron_tpu.ir.schema import Schema, to_arrow_schema, to_arrow_type
from auron_tpu.ops.base import Operator, TaskContext, batch_size


class OrcScanExec(Operator):
    def __init__(self, schema: Schema, file_groups: Tuple[FileGroup, ...],
                 projection: Tuple[int, ...] = (), predicate=None,
                 positional_evolution: bool = False):
        proj = tuple(projection) or tuple(range(len(schema)))
        super().__init__(schema.select(proj), [])
        self.file_schema = schema
        self.file_groups = tuple(file_groups)
        self.projection = proj
        self.predicate = predicate
        self.positional_evolution = positional_evolution

    def execute(self, ctx: TaskContext) -> Iterator[Batch]:
        return self._read(
            ctx, lambda rb: Batch.from_arrow(rb, schema=self.schema))

    def execute_arrow(self, ctx: TaskContext) -> Iterator[pa.RecordBatch]:
        return self._read(ctx, None)

    def _read(self, ctx: TaskContext, to_device) -> Iterator[Any]:
        """The read loop: record batches evolved to the plan's schema,
        each handed on as `to_device` makes it, or as it is."""
        from pyarrow import orc
        if ctx.partition_id >= len(self.file_groups):
            return  # extra partitions are empty
        gi = ctx.partition_id
        from auron_tpu.faults import fault_point
        from auron_tpu.ops.scan.parquet import _open_for_read
        for path in self.file_groups[gi].paths:
            # outside the corrupted-file catch, mirroring the parquet
            # scan: injected io faults go to the retry tier, they are
            # never swallowed as skipped files
            fault_point("scan.orc.open")
            try:
                f = orc.ORCFile(_open_for_read(path))
            except Exception:
                if conf.get("auron.ignore.corrupted.files"):
                    continue
                raise
            fault_point("scan.orc.read")
            tbl = f.read()
            out = self._evolve(tbl)
            for rb in out.to_batches(max_chunksize=batch_size()):
                yield rb if to_device is None else to_device(rb)

    def _evolve(self, tbl: pa.Table) -> pa.Table:
        from auron_tpu.config import conf
        arrays = []
        case_sensitive = bool(conf.get("auron.orc.schema.case.sensitive"))
        fnames = list(tbl.schema.names) if case_sensitive else \
            [n.lower() for n in tbl.schema.names]
        for out_pos, i in enumerate(self.projection):
            f = self.file_schema[i]
            at = to_arrow_type(f.dtype)
            if self.positional_evolution:
                col = tbl.column(i) if i < tbl.num_columns else None
            else:
                try:
                    idx = fnames.index(f.name if case_sensitive
                                       else f.name.lower())
                    col = tbl.column(idx)
                except ValueError:
                    col = None
            if col is None:
                arrays.append(pa.nulls(tbl.num_rows, type=at))
            else:
                c = col.combine_chunks()
                arrays.append(c.cast(at) if c.type != at else c)
        return pa.Table.from_arrays(arrays, schema=to_arrow_schema(self.schema))


class OrcSinkExec(Operator):
    def __init__(self, child: Operator, output_dir: str,
                 partition_cols: Tuple[str, ...] = (),
                 compression: str = "zstd", props=()):
        from auron_tpu.ir.schema import DataType, Field
        super().__init__(Schema((Field("path", DataType.string()),
                                 Field("rows", DataType.int64()))), [child])
        self.child_op = child
        self.output_dir = output_dir
        self.partition_cols = tuple(partition_cols)
        self.compression = compression

    def execute(self, ctx: TaskContext) -> Iterator[Batch]:
        import os
        from pyarrow import orc
        os.makedirs(self.output_dir, exist_ok=True)
        # ORC writer wants whole tables per partition dir
        parts = {}
        for b in self.child_stream(ctx):
            if b.num_rows == 0:
                continue
            rb = b.to_arrow()
            from auron_tpu.ops.scan.parquet import split_dynamic_partitions
            for key, part in split_dynamic_partitions(rb, self.partition_cols):
                parts.setdefault(key, []).append(part)
        rows = []
        for key, batches in parts.items():
            from auron_tpu.formats import fs as FS
            d = os.path.join(self.output_dir, *key)
            FS.makedirs(d)
            path = os.path.join(d, f"part-{ctx.partition_id:05d}.orc")
            tbl = pa.Table.from_batches(batches)
            if FS.is_remote(path):
                with FS.open_output(path) as f:
                    orc.write_table(tbl, f,
                                    compression=_orc_codec(self.compression))
            else:
                orc.write_table(tbl, path,
                                compression=_orc_codec(self.compression))
            rows.append({"path": path, "rows": tbl.num_rows})
        if rows:
            yield Batch.from_arrow(pa.Table.from_pylist(
                rows, schema=to_arrow_schema(self.schema))
                .combine_chunks().to_batches()[0])


def _orc_codec(c: str) -> str:
    return {"zstd": "zstd", "zlib": "zlib", "snappy": "snappy",
            "none": "uncompressed"}.get(c, "zstd")

"""Seeded TPC-DS tables, written as parquet, and the catalog that hands out
their scan nodes.

One general generator: each table is a module `benchmarks/tables/<name>.py`
(`generate(n_rows, rng, ctx, columns)`, `CHUNKS`), found by the name a
query lists; its cardinality is the configuration's (`rows`, from the
spec's table 3-2).  Only the tables the query scans are written; a table may
leave unbuilt the columns that `columns` does not name (the fact table does).

Two random streams.  The database's structure (keys, tickets, dates, null
masks, dimension rows: everything that sizes the work, that an index or a
join reads) is the configuration's: `rng`, seeded by (`data_seed` of the
configuration's file, table name), one database a scale as dsdgen's fixed
RNGSEED gives one.  The amounts a query sums and averages are the run's:
`ctx.amounts_rng(table)`, seeded by (`--seed`, table name).  So every seed
scans, probes and groups the same rows in the same places, and gets other
numbers to add up; the answer differs from seed to seed and the work does
not (PERF.md section 6, PR 25's second fix: on the chip the same program
took 13.09 to 13.34 s a query depending on what the keys were).
"""

from __future__ import annotations

import importlib
import os
import zlib
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence

import numpy as np
import pyarrow as pa
import pyarrow.parquet as pq

from auron_tpu.frontend.foreign import ForeignExpr, ForeignNode
from auron_tpu.ir.schema import Schema, from_arrow_schema


def table_module(name: str):
    return importlib.import_module(f"benchmarks.tables.{name}")


@dataclass
class TableDef:
    name: str
    schema: Schema
    chunks: List[str] = field(default_factory=list)   # parquet paths
    rows: int = 0
    column_bytes: Dict[str, int] = field(default_factory=dict)  # Arrow's


@dataclass
class Catalog:
    """Schema and parquet chunks of every table written, and the
    FileSourceScanExec node a Spark bridge would hand over for one."""

    data_dir: str
    tables: Dict[str, TableDef] = field(default_factory=dict)

    def scan(self, table: str, columns: Optional[Sequence[str]] = None,
             pushed_filters: Sequence[ForeignExpr] = ()) -> ForeignNode:
        t = self.tables[table]
        cols = list(columns) if columns is not None else t.schema.names()
        fields = {f.name: f for f in t.schema.fields}
        return ForeignNode(
            "FileSourceScanExec",
            output=Schema(tuple(fields[c] for c in cols)),
            attrs={"format": "parquet",
                   "file_groups": [[p] for p in t.chunks],
                   "pushed_filters": list(pushed_filters)})

    def read(self, table: str, columns: Sequence[str]) -> pa.Table:
        """The written rows back, for the reference."""
        return pa.concat_tables(
            pq.read_table(p, columns=list(columns))
            for p in self.tables[table].chunks)


class _Context:
    """What a table's generator may ask: the other tables' cardinalities,
    and the run's stream for the amounts it draws."""

    def __init__(self, rows: Dict[str, int], data_seed: int, seed: int):
        self._rows, self.data_seed, self.seed = rows, data_seed, seed

    def rows(self, name: str) -> int:
        return int(self._rows[name])

    def amounts_rng(self, name: str) -> np.random.Generator:
        return np.random.default_rng(
            [abs(int(self.seed)), zlib.crc32(name.encode()), 1])

    def table(self, name: str, columns: Sequence[str]) -> pa.Table:
        rng = np.random.default_rng(
            [abs(int(self.data_seed)), zlib.crc32(name.encode())])
        return table_module(name).generate(self.rows(name), rng, self,
                                           columns=list(columns))


def _least_bytes(column: pa.ChunkedArray) -> int:
    """The bytes a reader of the column cannot do without: Arrow's, but a
    decimal of up to 18 digits counted as the 8 bytes it needs and not the
    16 Arrow gives it."""
    if pa.types.is_decimal(column.type) and column.type.precision <= 18:
        return 8 * len(column)
    return column.nbytes


def _write_chunks(out_dir: str, name: str, table: pa.Table,
                  n_chunks: int) -> TableDef:
    tdir = os.path.join(out_dir, name)
    os.makedirs(tdir, exist_ok=True)
    n = table.num_rows
    bounds = np.linspace(0, n, max(1, min(n_chunks, n)) + 1).astype(int)
    paths = []
    for i, (lo, hi) in enumerate(zip(bounds[:-1], bounds[1:])):
        path = os.path.join(tdir, f"part-{i:05d}.parquet")
        pq.write_table(table.slice(lo, hi - lo), path)
        paths.append(path)
    return TableDef(name=name, schema=from_arrow_schema(table.schema),
                    chunks=paths, rows=n,
                    column_bytes={c: _least_bytes(table[c])
                                  for c in table.column_names})


def generate(data_dir: str, scans: Dict[str, Sequence[str]],
             rows: Dict[str, int], data_seed: int, seed: int) -> Catalog:
    """Write the tables `scans` names (table -> columns), each with the
    cardinality `rows` gives it, under `data_dir`: the structure from
    `data_seed`, the amounts from `seed`."""
    ctx = _Context(rows, data_seed, seed)
    cat = Catalog(data_dir=data_dir)
    for name, columns in scans.items():
        cat.tables[name] = _write_chunks(
            data_dir, name, ctx.table(name, columns),
            table_module(name).CHUNKS)
    return cat

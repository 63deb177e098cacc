"""Device batch: fixed-capacity padded columns + validity + row count.

Invariants (the contract every kernel relies on):
- every device array's leading dim == `capacity` (a power of two);
- rows with index >= num_rows are *padding*: validity False, data zeroed;
- null/pad positions hold canonical zeros (no NaN poisoning in reductions);
- `num_rows` is a host int (known after the producing op), but kernels
  receive it as a traced scalar so XLA never specializes on it.

This file replaces the Arrow-RecordBatch-centric plumbing of the reference's
datafusion-ext-commons (batch serde, batch size heuristics, lib.rs:74-100)
with a TPU-native representation; Arrow remains the host-side interchange
(arrow_interop.py).
"""

from __future__ import annotations

from dataclasses import dataclass, field, replace
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from auron_tpu.config import conf
from auron_tpu.ir.schema import DataType, Field, Schema, TypeId
from auron_tpu.runtime import jitcheck

# ONE gather program serves every batch structure (jax.jit's per-aval
# cache holds each column layout's compiled form)
jitcheck.waive_retraces(
    "batch.gather", 0, "one gather program per batch structure by design")

Array = Any  # jnp.ndarray


def bucket_capacity(n: int) -> int:
    """Smallest power-of-two capacity >= n (bounded below by config)."""
    cap = int(conf.get("auron.batch.capacity.min"))
    n = max(int(n), 1)
    while cap < n:
        cap <<= 1
    return cap


def bucket_width(w: int) -> int:
    """Smallest configured string width bucket >= w."""
    buckets = [int(x) for x in str(conf.get("auron.string.width.buckets")).split(",")]
    for b in buckets:
        if w <= b:
            return b
    return buckets[-1]


# ---------------------------------------------------------------------------
# columns
# ---------------------------------------------------------------------------

@dataclass
class DeviceColumn:
    """Flat (fixed-width) column: data[capacity], validity[capacity].

    `bits` (FLOAT64 only, optional): uint64[capacity] exact IEEE-754 bit
    patterns captured on the HOST at ingest.  On backends that demote f64
    (TPU), `data` is f32-granular — `bits` preserves full 64-bit ordering/
    equality/hashing semantics (sort_keys.py consumes it).  None on
    CPU/GPU (data itself is exact) and for device-COMPUTED columns (whose
    values are f32-exact anyway, so their bits are recovered losslessly by
    widening — sort_keys.f32_bits_to_f64_bits)."""
    dtype: DataType
    data: Array
    validity: Array  # bool[capacity]
    bits: Optional[Array] = None  # uint64[capacity] | None

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    def gather(self, indices: Array, valid: Array) -> "DeviceColumn":
        """Row gather with an index-validity mask (padding => null+zero)."""
        d = jnp.where(valid, jnp.take(self.data, indices, axis=0,
                                      mode="fill", fill_value=0), 0)
        v = jnp.where(valid, jnp.take(self.validity, indices, axis=0,
                                      mode="fill", fill_value=False), False)
        b = None
        if self.bits is not None:
            b = jnp.where(valid, jnp.take(self.bits, indices, axis=0,
                                          mode="fill", fill_value=0),
                          jnp.uint64(0))
        return DeviceColumn(self.dtype, d, v, b)

    def astuple(self):
        return (self.data, self.validity)


@dataclass
class DeviceStringColumn:
    """Fixed-width padded string/binary column.

    data[capacity, width] uint8 (zero-padded), lengths[capacity] int32,
    validity[capacity] bool.  Width is a config bucket; strings longer than
    auron.string.device.max.width never enter this representation (they stay
    host-resident as a HostColumn).
    """
    dtype: DataType
    data: Array       # uint8 [capacity, width]
    lengths: Array    # int32 [capacity]
    validity: Array   # bool [capacity]

    @property
    def capacity(self) -> int:
        return int(self.data.shape[0])

    @property
    def width(self) -> int:
        return int(self.data.shape[1])

    def gather(self, indices: Array, valid: Array) -> "DeviceStringColumn":
        d = jnp.where(valid[:, None],
                      jnp.take(self.data, indices, axis=0, mode="fill",
                               fill_value=0), 0)
        l = jnp.where(valid, jnp.take(self.lengths, indices, axis=0,
                                      mode="fill", fill_value=0), 0)
        v = jnp.where(valid, jnp.take(self.validity, indices, axis=0,
                                      mode="fill", fill_value=False), False)
        return DeviceStringColumn(self.dtype, d, l, v)

    def astuple(self):
        return (self.data, self.lengths, self.validity)


@dataclass
class DeviceDecimal128Column:
    """A DECIMAL of 19-38 digits in the stage program: the unscaled value
    as a 128-bit two's-complement integer in two 64-bit words a row,
    `hi` int64[capacity] (the signed high word) and `lo` uint64[capacity]
    (the low word), value = hi * 2**64 + lo, beside the validity word every
    column has; null and padding rows hold zeros.  THE layout: Arrow's
    decimal128 is the same sixteen bytes (low word first), so ingest and
    fetch are views (arrow_interop.py), and exprs/decimal128.py is the
    arithmetic over it.  It lives in the stage program alone
    (`stage_holds`); the serial engine keeps the type on the host
    (`is_device_type`)."""
    dtype: DataType
    hi: Array         # int64 [capacity]
    lo: Array         # uint64 [capacity]
    validity: Array   # bool [capacity]

    @property
    def capacity(self) -> int:
        return int(self.hi.shape[0])

    def gather(self, indices: Array, valid: Array) -> "DeviceDecimal128Column":
        h = jnp.where(valid, jnp.take(self.hi, indices, axis=0,
                                      mode="fill", fill_value=0), 0)
        l = jnp.where(valid, jnp.take(self.lo, indices, axis=0,
                                      mode="fill", fill_value=0),
                      jnp.uint64(0))
        v = jnp.where(valid, jnp.take(self.validity, indices, axis=0,
                                      mode="fill", fill_value=False), False)
        return DeviceDecimal128Column(self.dtype, h, l, v)

    def masked(self, keep: Array) -> "DeviceDecimal128Column":
        """Rows outside `keep` become null (and zero)."""
        return DeviceDecimal128Column(
            self.dtype, jnp.where(keep, self.hi, 0),
            jnp.where(keep, self.lo, jnp.uint64(0)),
            jnp.logical_and(self.validity, keep))

    @staticmethod
    def nulls(dtype: DataType, capacity: int) -> "DeviceDecimal128Column":
        return DeviceDecimal128Column(
            dtype, jnp.zeros(capacity, jnp.int64),
            jnp.zeros(capacity, jnp.uint64), jnp.zeros(capacity, bool))


@dataclass
class HostColumn:
    """Host-resident column for nested / oversized values (pyarrow array of
    length num_rows, NOT padded).  The hybrid-execution escape hatch."""
    dtype: DataType
    array: Any  # pyarrow.Array, len == num_rows of owning batch

    @property
    def capacity(self) -> int:  # logical; host cols are unpadded
        return len(self.array)

    def gather_host(self, indices: np.ndarray) -> "HostColumn":
        import pyarrow as pa
        import pyarrow.compute as pc
        idx = pa.array(indices.astype(np.int64), type=pa.int64())
        return HostColumn(self.dtype, pc.take(self.array, idx))

    def pylist(self) -> list:
        """Memoized to_pylist: host-path kernels (hash, key compare) may
        touch the same column once per chunk — convert once."""
        cached = getattr(self, "_pylist", None)
        if cached is None:
            cached = self.array.to_pylist()
            self._pylist = cached
        return cached


Column = Union[DeviceColumn, DeviceStringColumn, DeviceDecimal128Column,
               HostColumn]


# ---------------------------------------------------------------------------
# batch
# ---------------------------------------------------------------------------

class Batch:
    """num_rows may be a host int OR a device scalar ("lazy batch").  A
    lazy count lets a producer emit without a device->host sync; reading
    `.num_rows` fetches and caches it, and
    sync-free consumers use `.num_rows_dev()` / `.row_mask()` instead.
    This is the engine's answer to the reference's mpsc(1) pipelining
    (rt.rs:141-238): nothing blocks on the device until a host decision
    actually needs a value."""

    __slots__ = ("schema", "columns", "_num_rows", "capacity")

    def __init__(self, schema: Schema, columns: List[Column],
                 num_rows, capacity: int):
        assert len(columns) == len(schema), \
            f"{len(columns)} columns vs schema {schema!r}"
        self.schema = schema
        self.columns = columns
        self._num_rows = num_rows
        self.capacity = capacity

    @property
    def num_rows(self) -> int:
        if not isinstance(self._num_rows, (int, np.integer)):
            from auron_tpu.ops.kernel_cache import host_sync
            self._num_rows = int(host_sync(self._num_rows))
        return int(self._num_rows)

    @property
    def num_rows_known(self) -> bool:
        return isinstance(self._num_rows, (int, np.integer))

    @property
    def num_rows_raw(self):
        """The count as-is (host int OR device scalar), for constructing
        derived batches without forcing a sync."""
        return self._num_rows

    def num_rows_dev(self):
        """Row count as a jit-ready int32 scalar (no sync)."""
        n = self._num_rows
        if isinstance(n, (int, np.integer)):
            # a numpy scalar feeds jit/eager ops directly — calling
            # jnp.asarray here would pay an eager convert_element_type
            # dispatch per call (profiled at ~25% of a warm q01 run)
            return np.int32(n)
        if isinstance(n, jnp.ndarray) and n.dtype == jnp.int32:
            return n
        return jnp.asarray(n, jnp.int32)

    # -- constructors -------------------------------------------------------

    @staticmethod
    def empty(schema: Schema, capacity: Optional[int] = None) -> "Batch":
        cap = capacity or bucket_capacity(0)
        cols: List[Column] = []
        for f in schema:
            cols.append(_empty_column(f.dtype, cap))
        return Batch(schema, cols, 0, cap)

    @staticmethod
    def from_numpy(schema: Schema, arrays: Sequence[np.ndarray],
                   validities: Optional[Sequence[Optional[np.ndarray]]] = None,
                   capacity: Optional[int] = None) -> "Batch":
        """Build a device batch from host numpy columns (flat types; strings
        via numpy object/str arrays are routed through arrow_interop)."""
        n = len(arrays[0]) if arrays else 0
        cap = capacity or bucket_capacity(n)
        cols: List[Column] = []
        for i, f in enumerate(schema):
            a = np.asarray(arrays[i])
            v = None if validities is None else validities[i]
            if v is None:
                v = np.ones(n, dtype=bool)
            cols.append(_device_column_from_numpy(f.dtype, a, v, cap))
        return Batch(schema, cols, n, cap)

    # -- row-count helpers --------------------------------------------------

    def row_mask(self) -> Array:
        """bool[capacity]: True for live rows (no sync)."""
        return jnp.arange(self.capacity, dtype=jnp.int32) < self.num_rows_dev()

    # -- transforms ---------------------------------------------------------

    def select(self, indices: Sequence[int]) -> "Batch":
        return Batch(self.schema.select(indices),
                     [self.columns[i] for i in indices],
                     self._num_rows, self.capacity)

    def rename(self, names: Sequence[str]) -> "Batch":
        return Batch(self.schema.rename(tuple(names)), self.columns,
                     self._num_rows, self.capacity)

    def with_columns(self, schema: Schema, columns: List[Column]) -> "Batch":
        return Batch(schema, columns, self._num_rows, self.capacity)

    def gather(self, indices: Array, num_rows: int,
               capacity: Optional[int] = None) -> "Batch":
        """Gather rows by device index vector (shape [out_capacity]); rows
        beyond num_rows in the index vector are padding.  Device columns go
        through one cached jitted kernel (kernel_cache) instead of eager
        per-column dispatch."""
        from auron_tpu.ops.kernel_cache import cached_jit, host_sync
        out_cap = capacity or int(indices.shape[0])
        dev_idx = [i for i, c in enumerate(self.columns)
                   if not isinstance(c, HostColumn)]
        gathered: Dict[int, Column] = {}
        if dev_idx:
            kernel = cached_jit("batch.gather", _gather_kernel_builder)
            outs = kernel([self.columns[i] for i in dev_idx], indices,
                          jnp.asarray(num_rows, jnp.int32))
            gathered = dict(zip(dev_idx, outs))
        host_idx: Optional[np.ndarray] = None
        cols: List[Column] = []
        for i, c in enumerate(self.columns):
            if isinstance(c, HostColumn):
                if host_idx is None:
                    host_idx = np.asarray(host_sync(indices))[:num_rows]
                cols.append(c.gather_host(host_idx))
            else:
                cols.append(gathered[i])
        return Batch(self.schema, cols, num_rows, out_cap)

    def head(self, n: int) -> "Batch":
        """Logical truncation (no data movement): clamp num_rows and fix
        validity beyond n."""
        n = min(n, self.num_rows)
        mask = jnp.arange(self.capacity, dtype=jnp.int32) < jnp.int32(n)
        cols: List[Column] = []
        for c in self.columns:
            if isinstance(c, HostColumn):
                cols.append(HostColumn(c.dtype, c.array.slice(0, n)))
            elif isinstance(c, DeviceStringColumn):
                cols.append(DeviceStringColumn(
                    c.dtype, jnp.where(mask[:, None], c.data, 0),
                    jnp.where(mask, c.lengths, 0),
                    jnp.logical_and(c.validity, mask)))
            else:
                cols.append(DeviceColumn(
                    c.dtype, jnp.where(mask, c.data, _zero_like(c.data)),
                    jnp.logical_and(c.validity, mask),
                    None if c.bits is None else
                    jnp.where(mask, c.bits, jnp.uint64(0))))
        return Batch(self.schema, cols, n, self.capacity)

    def mem_bytes(self) -> int:
        """Approximate device bytes held by this batch."""
        total = 0
        for c in self.columns:
            if isinstance(c, DeviceColumn):
                total += c.data.size * c.data.dtype.itemsize + c.validity.size
            elif isinstance(c, DeviceStringColumn):
                total += c.data.size + c.lengths.size * 4 + c.validity.size
            elif isinstance(c, HostColumn):
                total += c.array.nbytes
        return int(total)

    def has_host_columns(self) -> bool:
        return any(isinstance(c, HostColumn) for c in self.columns)

    # -- conversion shortcuts ----------------------------------------------

    def to_arrow(self):
        from auron_tpu.columnar.arrow_interop import batch_to_arrow
        return batch_to_arrow(self)

    @staticmethod
    def from_arrow(rb, capacity: Optional[int] = None,
                   schema: Optional[Schema] = None) -> "Batch":
        from auron_tpu.columnar.arrow_interop import arrow_to_batch
        return arrow_to_batch(rb, capacity=capacity, schema=schema)

    def to_pylist(self) -> List[dict]:
        return self.to_arrow().to_pylist()


# ---------------------------------------------------------------------------
# helpers
# ---------------------------------------------------------------------------

def _zero_like(a: Array):
    return jnp.zeros((), dtype=a.dtype)


def _gather_kernel_builder():
    def run(cols, indices, num_rows):
        valid = jnp.arange(indices.shape[0], dtype=jnp.int32) < num_rows
        return [c.gather(indices, valid) for c in cols]
    return run


def concat_device_columns(parts: List[Any]):
    """Device concat of the same logical column across batches (pure jax;
    string widths are padded to the widest part)."""
    if isinstance(parts[0], DeviceDecimal128Column):
        return DeviceDecimal128Column(
            parts[0].dtype, jnp.concatenate([p.hi for p in parts]),
            jnp.concatenate([p.lo for p in parts]),
            jnp.concatenate([p.validity for p in parts]))
    if isinstance(parts[0], DeviceStringColumn):
        w = max(p.data.shape[1] for p in parts)
        datas = [jnp.pad(p.data, ((0, 0), (0, w - p.data.shape[1])))
                 if p.data.shape[1] < w else p.data for p in parts]
        return DeviceStringColumn(
            parts[0].dtype, jnp.concatenate(datas),
            jnp.concatenate([p.lengths for p in parts]),
            jnp.concatenate([p.validity for p in parts]))
    bits = None
    if any(p.bits is not None for p in parts):
        # normalize: parts without exact bits widen from their (f32-exact)
        # values so one column never mixes key spaces
        from auron_tpu.ops.sort_keys import f64_bits_of_column
        bits = jnp.concatenate([p.bits if p.bits is not None
                                else f64_bits_of_column(p) for p in parts])
    return DeviceColumn(parts[0].dtype,
                        jnp.concatenate([p.data for p in parts]),
                        jnp.concatenate([p.validity for p in parts]), bits)


def is_device_type(dt: DataType) -> bool:
    """Does the serial engine hold this logical type on the device?  Not
    what `DataType.host_resident` names: nested values and wide decimals
    are `HostColumn`s there."""
    return not dt.host_resident


def stage_holds(dt: DataType) -> bool:
    """Can the stage program hold this logical type?  What the serial
    engine holds on the device, and wide decimals
    (`DeviceDecimal128Column`)."""
    return is_device_type(dt) or dt.is_wide_decimal


def _empty_column(dt: DataType, cap: int) -> Column:
    if not is_device_type(dt):
        import pyarrow as pa
        from auron_tpu.ir.schema import to_arrow_type
        return HostColumn(dt, pa.array([], type=to_arrow_type(dt)))
    if dt.is_stringlike:
        w = bucket_width(1)
        return DeviceStringColumn(
            dt, jnp.zeros((cap, w), dtype=jnp.uint8),
            jnp.zeros(cap, dtype=jnp.int32), jnp.zeros(cap, dtype=bool))
    return DeviceColumn(dt, jnp.zeros(cap, dtype=dt.numpy_dtype()),
                        jnp.zeros(cap, dtype=bool))


def _device_column_from_numpy(dt: DataType, a: np.ndarray, v: np.ndarray,
                              cap: int) -> Column:
    if dt.is_stringlike or a.dtype.kind in ("U", "S", "O"):
        from auron_tpu.columnar.arrow_interop import numpy_strings_to_column
        return numpy_strings_to_column(dt, a, v, cap)
    n = len(a)
    data = np.zeros(cap, dtype=dt.numpy_dtype())
    data[:n] = np.where(v, a.astype(dt.numpy_dtype(), copy=False), 0)
    valid = np.zeros(cap, dtype=bool)
    valid[:n] = v
    bits = None
    if dt.id == TypeId.FLOAT64:
        from auron_tpu.ops.sort_keys import f64_exact_bits_enabled
        if f64_exact_bits_enabled():
            # capture the exact IEEE bits on the host (free: a view) so
            # TPU ordering/grouping/hashing stays 64-bit-exact even though
            # the device value is demoted to f32 granularity
            bits = jnp.asarray(data.view(np.uint64))
    return DeviceColumn(dt, jnp.asarray(data), jnp.asarray(valid), bits)


# ---------------------------------------------------------------------------
# pytree registration: device columns flow through jax.jit directly (dtype is
# static aux data; DataType is a frozen dataclass => hashable).  Batch itself
# stays host-side; operators pass column lists + a traced num_rows scalar.
# ---------------------------------------------------------------------------

jax.tree_util.register_pytree_node(
    DeviceColumn,
    # aux carries whether `bits` rides along so the children tuple arity
    # stays static per-structure (jit caches key on the treedef)
    lambda c: (((c.data, c.validity) if c.bits is None
                else (c.data, c.validity, c.bits)), (c.dtype, c.bits is not None)),
    lambda aux, kids: DeviceColumn(aux[0], *kids),
)
jax.tree_util.register_pytree_node(
    DeviceDecimal128Column,
    lambda c: ((c.hi, c.lo, c.validity), c.dtype),
    lambda dtype, kids: DeviceDecimal128Column(dtype, *kids),
)
jax.tree_util.register_pytree_node(
    DeviceStringColumn,
    lambda c: ((c.data, c.lengths, c.validity), c.dtype),
    lambda dtype, kids: DeviceStringColumn(dtype, *kids),
)


def concat_batches(schema: Schema, batches: List[Batch],
                   capacity: Optional[int] = None) -> Batch:
    """Concatenate along rows into one padded batch (device concat; host
    columns concat via pyarrow)."""
    import pyarrow as pa
    total = sum(b.num_rows for b in batches)
    cap = capacity or bucket_capacity(total)
    assert cap >= total, f"concat capacity {cap} < total rows {total}"
    if not batches:
        return Batch.empty(schema, cap)
    cols: List[Column] = []
    for ci, f in enumerate(schema):
        parts = [b.columns[ci] for b in batches]
        if any(isinstance(p, HostColumn) for p in parts):
            # representation can differ per batch (oversize strings demote
            # to host); normalize the whole column to host
            from auron_tpu.columnar.arrow_interop import column_to_arrow
            arrs = []
            for b, p in zip(batches, parts):
                a = p.array if isinstance(p, HostColumn) else \
                    column_to_arrow(f.dtype, p, b.num_rows)
                if isinstance(a, pa.ChunkedArray):
                    a = a.combine_chunks()
                arrs.append(a)
            t0 = arrs[0].type
            arrs = [a.cast(t0) if a.type != t0 else a for a in arrs]
            cols.append(HostColumn(f.dtype, pa.concat_arrays(arrs)))
        elif isinstance(parts[0], DeviceStringColumn):
            w = max(p.width for p in parts)
            datas, lens, vals = [], [], []
            for b, p in zip(batches, parts):
                d = p.data
                if p.width < w:
                    d = jnp.pad(d, ((0, 0), (0, w - p.width)))
                datas.append(d[:b.num_rows])
                lens.append(p.lengths[:b.num_rows])
                vals.append(p.validity[:b.num_rows])
            data = jnp.concatenate(datas)[:cap]
            data = jnp.pad(data, ((0, cap - data.shape[0]), (0, 0)))
            ln = jnp.concatenate(lens)[:cap]
            ln = jnp.pad(ln, (0, cap - ln.shape[0]))
            va = jnp.concatenate(vals)[:cap]
            va = jnp.pad(va, (0, cap - va.shape[0]))
            cols.append(DeviceStringColumn(f.dtype, data, ln, va))
        else:
            datas = [p.data[:b.num_rows] for b, p in zip(batches, parts)]
            vals = [p.validity[:b.num_rows] for b, p in zip(batches, parts)]
            data = jnp.concatenate(datas)[:cap]
            data = jnp.pad(data, (0, cap - data.shape[0]))
            va = jnp.concatenate(vals)[:cap]
            va = jnp.pad(va, (0, cap - va.shape[0]))
            bits = None
            if any(p.bits is not None for p in parts):
                from auron_tpu.ops.sort_keys import f64_bits_of_column
                bs = [(p.bits if p.bits is not None
                       else f64_bits_of_column(p))[:b.num_rows]
                      for b, p in zip(batches, parts)]
                bits = jnp.concatenate(bs)[:cap]
                bits = jnp.pad(bits, (0, cap - bits.shape[0]))
            cols.append(DeviceColumn(f.dtype, data, va, bits))
    return Batch(schema, cols, total, cap)

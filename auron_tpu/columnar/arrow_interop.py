"""Arrow <-> device batch conversion.

The host-side columnar interchange is Arrow (pyarrow), matching the
reference's use of arrow-rs + the Arrow C-Data FFI at the JVM boundary
(auron-core AuronArrowFFIExporter.java / ffi_reader_exec.rs:46).  A JVM (or
any Arrow producer) hands batches across via the C-Data interface —
`pyarrow.RecordBatch._import_from_c` — and this module moves them into the
padded device representation.

Conversions are vectorized numpy (no per-row Python):
- flat types: fill_null + astype + pad
- decimal128(p<=18): unscaled int64 extracted from the 16-byte LE values
- strings/binary: offsets+data -> fixed-width padded [cap, W] uint8 matrix
- nested / decimal(p>18) / oversize strings: host-resident passthrough; for
  the stage program (`wide=True`) decimal(p>18) is the two words of each
  16-byte value (batch.py `DeviceDecimal128Column`)
"""

from __future__ import annotations

from typing import List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa

from auron_tpu.config import conf
from auron_tpu.columnar.batch import (
    Batch, Column, DeviceColumn, DeviceDecimal128Column, DeviceStringColumn,
    HostColumn, bucket_capacity, bucket_width, is_device_type, stage_holds,
)
from auron_tpu.ir.schema import (
    DataType, Schema, TypeId, from_arrow_schema, to_arrow_schema, to_arrow_type,
)


# ---------------------------------------------------------------------------
# arrow -> device
# ---------------------------------------------------------------------------

def arrow_to_batch(rb: pa.RecordBatch, capacity: Optional[int] = None,
                   schema: Optional[Schema] = None) -> Batch:
    if isinstance(rb, pa.Table):
        rb = rb.combine_chunks().to_batches()[0] if rb.num_rows else \
            pa.RecordBatch.from_pylist([], schema=rb.schema)
    schema = schema or from_arrow_schema(rb.schema)
    n = rb.num_rows
    cap = capacity or bucket_capacity(n)
    cols: List[Column] = []
    for i, f in enumerate(schema):
        cols.append(arrow_array_to_column(f.dtype, rb.column(i), cap))
    return Batch(schema, cols, n, cap)


def arrow_array_to_column(dt: DataType, arr: pa.Array, cap: int) -> Column:
    if isinstance(arr, pa.ChunkedArray):
        arr = arr.combine_chunks()
    col = arrow_array_to_host_column(dt, arr, cap)
    if isinstance(col, HostColumn):
        return col
    return jax.tree.map(jnp.asarray, col)


def arrow_array_to_host_column(dt: DataType,
                               arr: Union[pa.Array, pa.ChunkedArray], cap: int,
                               dealt: Optional[Sequence[int]] = None,
                               wide: bool = False) -> Column:
    """The host half of `arrow_array_to_column`: the padded column with
    numpy leaves, or the verdict `HostColumn`.

    `arr` is an Array or a ChunkedArray, read chunk by chunk.  `dealt` says
    how many of its rows, in order, go to each of `len(dealt)` parts of
    `cap` slots (default: one part, all rows): part d's rows lie from slot
    d * cap on, and every slot past them is zero and invalid.  `wide`: the
    caller is the stage program, which holds a decimal of 19-38 digits as
    two words a value where the serial engine keeps it on the host."""
    from auron_tpu.columnar.serde import note_copy
    if not (stage_holds(dt) if wide else is_device_type(dt)):
        return HostColumn(dt, arr)
    n = len(arr)
    dealt = [n] if dealt is None else dealt
    slots = len(dealt) * cap
    # (chunk, its first slot), zero-copy slices of `arr`
    pieces: List[Tuple[pa.Array, int]] = []
    row = 0
    for d, rows in enumerate(dealt):
        part = arr.slice(row, rows)
        at = d * cap
        for chunk in (part.chunks if isinstance(part, pa.ChunkedArray)
                      else [part]):
            if len(chunk):
                pieces.append((chunk, at))
                at += len(chunk)
        row += rows
    validity = np.zeros(slots, dtype=bool)
    for chunk, at in pieces:
        validity[at: at + len(chunk)] = _arrow_validity(chunk)
    if dt.is_stringlike:
        note_copy("ingest.arrow.string")
        parts = [_arrow_string_parts(chunk) for chunk, _ in pieces]
        max_len = max((int(lengths.max()) for lengths, _ in parts),
                      default=0)
        if max_len > int(conf.get("auron.string.device.max.width")):
            return HostColumn(dt, arr)
        w = bucket_width(max(max_len, 1))
        mat = np.zeros((slots, w), dtype=np.uint8)
        ln = np.zeros(slots, dtype=np.int32)
        for (chunk, at), (lengths, flat) in zip(pieces, parts):
            rows = slice(at, at + len(chunk))
            row_ids, within, src = _scatter_indices(lengths, w)
            mat[rows][row_ids, within] = flat[src]
            mat[rows][~validity[rows]] = 0
            ln[rows] = np.where(validity[rows], lengths, 0)
        return DeviceStringColumn(dt, mat, ln, validity)
    if dt.is_wide_decimal:
        note_copy("ingest.arrow.fixed")
        hi = np.zeros(slots, dtype=np.int64)
        lo = np.zeros(slots, dtype=np.uint64)
        for chunk, at in pieces:
            rows = slice(at, at + len(chunk))
            words = _decimal128_words(chunk)
            np.copyto(lo[rows], words[:, 0].view(np.uint64),
                      where=validity[rows])
            np.copyto(hi[rows], words[:, 1], where=validity[rows])
        return DeviceDecimal128Column(dt, hi, lo, validity)
    # flat types: read raw fixed-width values straight from the Arrow values
    # buffer (null slots hold garbage, masked below), avoiding to_numpy's
    # object-dtype detours for date/timestamp/decimal.
    npdt = dt.numpy_dtype()
    data = np.zeros(slots, dtype=npdt)
    if n:
        note_copy("ingest.arrow.fixed")
    for chunk, at in pieces:
        rows = slice(at, at + len(chunk))
        if dt.id == TypeId.DECIMAL:
            vals = _decimal128_unscaled_int64(chunk)
        elif dt.id == TypeId.TIMESTAMP_US:
            if not (pa.types.is_timestamp(chunk.type)
                    and chunk.type.unit == "us"):
                chunk = chunk.cast(pa.timestamp("us"))
            vals = _primitive_values(chunk, np.int64)
        elif dt.id == TypeId.BOOL:
            vals = _bitpacked_values(chunk)
        else:
            if pa.types.is_dictionary(chunk.type):
                chunk = chunk.dictionary_decode()
            vals = _primitive_values(chunk, None).astype(npdt, copy=False)
        if chunk.null_count == 0:
            data[rows] = vals
        else:
            # `data` is zero where a null leaves it alone
            np.copyto(data[rows], vals, where=validity[rows])
    bits = None
    if dt.id == TypeId.FLOAT64:
        from auron_tpu.ops.sort_keys import f64_exact_bits_enabled
        if f64_exact_bits_enabled():
            bits = data.view(np.uint64)
    return DeviceColumn(dt, data, validity, bits)


def _arrow_validity(arr: pa.Array) -> np.ndarray:
    if arr.null_count == 0:
        return np.ones(len(arr), dtype=bool)
    return np.asarray(arr.is_valid())


_ARROW_NP = {
    "int8": np.int8, "int16": np.int16, "int32": np.int32, "int64": np.int64,
    "uint8": np.uint8, "uint16": np.uint16, "uint32": np.uint32,
    "uint64": np.uint64, "float": np.float32, "halffloat": np.float16,
    "double": np.float64, "date32[day]": np.int32, "date64[ms]": np.int64,
}


def _primitive_values(arr: pa.Array, npdt) -> np.ndarray:
    """Fixed-width values buffer view (null slots contain garbage)."""
    if npdt is None:
        key = str(arr.type)
        if key.startswith("timestamp"):
            npdt = np.int64
        elif key in _ARROW_NP:
            npdt = _ARROW_NP[key]
        else:
            raise TypeError(f"unsupported primitive arrow type {arr.type}")
    buf = arr.buffers()[1]
    return np.frombuffer(buf, dtype=npdt)[arr.offset: arr.offset + len(arr)]


def _bitpacked_values(arr: pa.Array) -> np.ndarray:
    # the bytes that hold the array's bits, not the whole buffer: a chunk
    # may be a short slice of a long one
    lo, hi = arr.offset // 8, -(-(arr.offset + len(arr)) // 8)
    buf = np.frombuffer(arr.buffers()[1], dtype=np.uint8)[lo:hi]
    bits = np.unpackbits(buf, bitorder="little")
    return bits[arr.offset - 8 * lo:][:len(arr)].astype(bool)


def _decimal128_words(arr: pa.Array) -> np.ndarray:
    """int64[n, 2] view of a decimal128 values buffer: 16-byte LE
    two's-complement, the low word first."""
    raw = np.frombuffer(arr.buffers()[1], dtype=np.int64)
    return raw[2 * arr.offset: 2 * (arr.offset + len(arr))].reshape(-1, 2)


def _decimal128_unscaled_int64(arr: pa.Array) -> np.ndarray:
    """For p<=18 the value fits the low word (the high word is the sign
    extension)."""
    return _decimal128_words(arr)[:, 0]


def _arrow_string_parts(arr: pa.Array) -> Tuple[np.ndarray, np.ndarray]:
    """(lengths int64[n], flat_bytes uint8[total]) with per-row start offsets
    folded into _scatter_indices via cumsum of lengths (nulls => length 0
    handled by validity)."""
    t = arr.type
    if not (pa.types.is_large_string(t) or pa.types.is_large_binary(t)
            or pa.types.is_string(t) or pa.types.is_binary(t)):
        arr = arr.cast(pa.large_binary())
        t = arr.type
    large = pa.types.is_large_string(t) or pa.types.is_large_binary(t)
    off_dt = np.int64 if large else np.int32
    bufs = arr.buffers()
    offsets = np.frombuffer(bufs[1], dtype=off_dt)[arr.offset: arr.offset + len(arr) + 1]
    data = np.frombuffer(bufs[2], dtype=np.uint8) if bufs[2] is not None \
        else np.zeros(0, dtype=np.uint8)
    lengths = (offsets[1:] - offsets[:-1]).astype(np.int64)
    if len(arr) == 0:
        return lengths, data[:0]
    # the flat buffer as seen from offsets[0] (slice handles array offset)
    return lengths, data[int(offsets[0]): int(offsets[-1])]


def _scatter_indices(lengths: np.ndarray, w: int):
    """Index vectors to scatter variable-length rows into an [n, w] matrix.

    Returns (row_ids, within, src): mat[row_ids, within] = flat[src], where
    src indexes the *compacted* flat buffer (rows laid out back-to-back).
    """
    clip = np.minimum(lengths, w)
    starts = np.cumsum(lengths) - lengths   # start of each row in flat buffer
    total = int(clip.sum())
    row_ids = np.repeat(np.arange(len(lengths)), clip)
    cum = np.cumsum(clip) - clip
    within = np.arange(total) - np.repeat(cum, clip)
    src = np.repeat(starts, clip) + within
    return row_ids, within, src


def numpy_strings_to_column(dt: DataType, a: np.ndarray, v: np.ndarray,
                            cap: int) -> Column:
    """Route numpy str/object arrays through pyarrow into the device repr."""
    at = to_arrow_type(dt)
    vals = [None if not v[i] else a[i] for i in range(len(a))]
    arr = pa.array(vals, type=at)
    return arrow_array_to_column(dt, arr, cap)


# ---------------------------------------------------------------------------
# device -> arrow
# ---------------------------------------------------------------------------

def batch_to_arrow(batch: Batch) -> pa.RecordBatch:
    """Device batch -> arrow.  All device buffers (and a lazy row count)
    are fetched in ONE host_sync call: per-column np.asarray would pay a
    full host round trip per buffer."""
    from auron_tpu.ops.kernel_cache import host_sync
    dev_idx = [i for i, c in enumerate(batch.columns)
               if not isinstance(c, HostColumn)]
    count, fetched = host_sync((batch.num_rows_raw,
                                [batch.columns[i] for i in dev_idx]))
    n = int(count)
    batch._num_rows = n
    cols = list(batch.columns)
    for i, c in zip(dev_idx, fetched):
        cols[i] = c
    arrays = []
    for f, c in zip(batch.schema, cols):
        arrays.append(column_to_arrow(f.dtype, c, n))
    return pa.RecordBatch.from_arrays(arrays, schema=to_arrow_schema(batch.schema))


def column_to_arrow(dt: DataType, col: Column, n: int) -> pa.Array:
    at = to_arrow_type(dt)
    if isinstance(col, HostColumn):
        a = col.array
        if isinstance(a, pa.ChunkedArray):
            a = a.combine_chunks()
        a = a.slice(0, n)
        return a.cast(at) if a.type != at else a
    if isinstance(col, DeviceStringColumn):
        mat = np.asarray(col.data)[:n]
        lengths = np.asarray(col.lengths)[:n].astype(np.int64)
        valid = np.asarray(col.validity)[:n]
        lengths = np.where(valid, lengths, 0)
        offsets = np.zeros(n + 1, dtype=np.int64)
        np.cumsum(lengths, out=offsets[1:])
        total = int(offsets[-1])
        flat = np.zeros(total, dtype=np.uint8)
        if total:
            row_ids = np.repeat(np.arange(n), lengths)
            cum = offsets[:-1]
            within = np.arange(total) - np.repeat(cum, lengths)
            flat = mat[row_ids, within]
        storage = pa.large_binary() if dt.id == TypeId.BINARY else pa.large_utf8()
        arr = pa.Array.from_buffers(
            storage, n,
            [pa.py_buffer(np.packbits(valid, bitorder="little").tobytes()),
             pa.py_buffer(offsets.tobytes()), pa.py_buffer(flat.tobytes())])
        return arr.cast(at) if arr.type != at else arr
    if isinstance(col, DeviceDecimal128Column):
        valid = np.asarray(col.validity)[:n]
        pairs = np.empty((n, 2), dtype=np.int64)
        pairs[:, 0] = np.asarray(col.lo)[:n].view(np.int64)
        pairs[:, 1] = np.asarray(col.hi)[:n]
        return pa.Array.from_buffers(
            at, n, [pa.py_buffer(np.packbits(valid, bitorder="little")
                                 .tobytes()), pa.py_buffer(pairs.tobytes())])
    # flat
    data = np.asarray(col.data)[:n]
    if dt.id == TypeId.FLOAT64 and getattr(col, "bits", None) is not None:
        # reconstruct the exact doubles from the ingest-captured bit
        # sidecar: the device value may be f32-demoted (TPU), and spill/
        # output must round-trip what was ingested, not the demotion
        data = np.asarray(col.bits)[:n].view(np.float64)
    valid = np.asarray(col.validity)[:n]
    mask = pa.py_buffer(np.packbits(valid, bitorder="little").tobytes())
    if dt.id == TypeId.DECIMAL:
        lo = data.astype(np.int64)
        hi = (lo >> 63).astype(np.int64)          # sign extension
        pairs = np.empty((n, 2), dtype=np.int64)
        pairs[:, 0], pairs[:, 1] = lo, hi
        arr = pa.Array.from_buffers(at, n, [mask, pa.py_buffer(pairs.tobytes())])
        return arr
    if dt.id == TypeId.BOOL:
        vals = pa.py_buffer(np.packbits(data.astype(bool),
                                        bitorder="little").tobytes())
        return pa.Array.from_buffers(pa.bool_(), n, [mask, vals])
    phys = {
        TypeId.DATE32: pa.int32(), TypeId.TIMESTAMP_US: pa.int64(),
    }.get(dt.id, at)
    arr = pa.Array.from_buffers(phys, n,
                                [mask, pa.py_buffer(np.ascontiguousarray(data).tobytes())])
    return arr.cast(at) if phys != at else arr

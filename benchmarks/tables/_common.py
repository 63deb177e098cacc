"""What the table generators share: dsdgen's business keys, decimal columns
from unscaled integers, and null masks."""

import numpy as np
import pyarrow as pa

_XLATE = np.frombuffer(b"ABCDEFGHIJKLMNOP", dtype=np.uint8)


def business_keys(index) -> pa.Array:
    """dsdgen's mk_bkey: 16 letters A-P, the high 32 bits of the index in the
    first eight and the low 32 bits in the last eight, low nibble first
    (1 -> AAAAAAAABAAAAAAA)."""
    v = np.asarray(index, dtype=np.uint64)
    out = np.empty((len(v), 16), dtype=np.uint8)
    for half, word in ((0, v >> np.uint64(32)), (8, v & np.uint64(0xFFFFFFFF))):
        for j in range(8):
            out[:, half + j] = _XLATE[
                ((word >> np.uint64(4 * j)) & np.uint64(0xF)).astype(np.intp)]
    offsets = np.arange(len(v) + 1, dtype=np.int32) * 16
    return pa.Array.from_buffers(
        pa.string(), len(v),
        [None, pa.py_buffer(offsets), pa.py_buffer(out.reshape(-1))])


def _validity(null_mask):
    if null_mask is None or not null_mask.any():
        return None, 0
    bits = np.packbits(~null_mask, bitorder="little")
    return pa.py_buffer(bits), int(null_mask.sum())


def decimal_array(unscaled, precision: int, scale: int,
                  null_mask=None) -> pa.Array:
    """decimal128(precision, scale) from unscaled int64 values."""
    v = np.asarray(unscaled, dtype=np.int64)
    pair = np.empty((len(v), 2), dtype=np.int64)
    pair[:, 0] = v
    pair[:, 1] = v >> 63          # the sign, extended
    validity, nulls = _validity(null_mask)
    return pa.Array.from_buffers(
        pa.decimal128(precision, scale), len(v),
        [validity, pa.py_buffer(pair.reshape(-1))], null_count=nulls)


def int_array(values, null_mask=None) -> pa.Array:
    if null_mask is None:
        return pa.array(values)
    return pa.array(values, mask=null_mask)


def choice_strings(words, codes) -> pa.Array:
    """words[codes] as a string column."""
    return pa.DictionaryArray.from_arrays(
        pa.array(np.asarray(codes, dtype=np.int32)),
        pa.array(list(words))).cast(pa.string())

"""Arithmetic the plain references share.  Imports nothing of the program.

`dtype` is the precision the reference computes in: float64 is what the
configurations state; the control of `correct` passes float32."""

import numpy as np
import pyarrow as pa


def group_codes(*keys):
    """Dense group number of each row for the given key columns, and one
    representative row index per group (groups in ascending key order)."""
    order = np.lexsort(keys[::-1])
    sorted_keys = [np.asarray(k)[order] for k in keys]
    new = np.ones(len(order), dtype=bool)
    if len(order):
        new[1:] = np.any([k[1:] != k[:-1] for k in sorted_keys], axis=0)
    codes = np.empty(len(order), dtype=np.int64)
    codes[order] = np.cumsum(new) - 1
    return codes, order[new]


def group_sum(values, codes, n_groups, dtype):
    """Sum of `values` per group, accumulated in `dtype` in row order.
    Every group has at least one row."""
    order = np.argsort(codes, kind="stable")
    starts = np.searchsorted(codes[order], np.arange(n_groups))
    return np.add.reduceat(np.asarray(values)[order].astype(dtype), starts)


def unscaled(column):
    """(unscaled int64 values, null mask) of an Arrow decimal128 column
    whose precision fits 64 bits: the low word of each 16-byte value."""
    arr = column.combine_chunks() if hasattr(column, "combine_chunks") \
        else column
    words = np.frombuffer(arr.buffers()[1], dtype=np.int64)
    low = words[2 * arr.offset:2 * (arr.offset + len(arr)):2]
    return low.copy(), nulls(arr)


def nulls(column):
    """Which rows of an Arrow column are null."""
    return column.is_null().to_numpy(zero_copy_only=False) \
        if isinstance(column, pa.Array) \
        else column.combine_chunks().is_null().to_numpy(zero_copy_only=False)


def ints(column, fill=0):
    """An Arrow column of integers as numpy, nulls as `fill`."""
    arr = column.combine_chunks() if hasattr(column, "combine_chunks") \
        else column
    return arr.fill_null(fill).to_numpy(zero_copy_only=False)


def half_up(numerator, denominator):
    """numerator / denominator, rounded half up (away from zero), over
    integers with a positive denominator."""
    sign = np.sign(numerator)
    return sign * ((2 * np.abs(numerator) + denominator)
                   // (2 * denominator))

"""Repartitioners: hash / round-robin / single / range.

Analogue of shuffle/mod.rs:112-279.  Partition ids are computed ON DEVICE:
- hash: pmod(murmur3(keys, seed=42), N) — bit-identical to Spark/the
  reference (shuffle/mod.rs:164-189), so mixed deployments shuffle alike;
- round_robin: (start + row_index) % N;
- range: binary search over sampled bounds encoded as sort-key words
  (driver-side sampling supplies `range_bounds`, like
  NativeShuffleExchangeBase.scala:313);
- single: all rows -> partition 0.
"""

from __future__ import annotations

from typing import Any, List, Tuple

import jax.numpy as jnp
import numpy as np

from auron_tpu.columnar.batch import Batch
from auron_tpu.exprs import hashing as H
from auron_tpu.exprs.compiler import build_evaluator
from auron_tpu.ir.plan import Partitioning
from auron_tpu.ir.schema import Schema


class PartitionIdComputer:
    """Compiled partition-id computation for one Partitioning spec."""

    def __init__(self, part: Partitioning, schema: Schema):
        self.part = part
        self.mode = part.mode
        self.n = part.num_partitions
        self._key_eval = None
        self._bounds_words = None
        if self.mode == "hash":
            self._key_eval = build_evaluator(part.expressions, schema)
        elif self.mode == "range":
            self._key_eval = build_evaluator(
                tuple(s.child for s in part.sort_orders), schema)
            self._orders = tuple((s.asc, s.nulls_first)
                                 for s in part.sort_orders)

    def __call__(self, batch: Batch, partition_id: int = 0,
                 row_start: int = 0):
        """-> int32[capacity] partition ids (padding rows get 0)."""
        cap = batch.capacity
        if self.mode == "single" or self.n <= 1:
            return jnp.zeros(cap, jnp.int32)
        if self.mode == "round_robin":
            ids = (jnp.arange(cap, dtype=jnp.int64) + row_start) % self.n
            return ids.astype(jnp.int32)
        if self.mode == "hash":
            # plain XLA murmur3+pmod: one fused elementwise chain
            keys = self._key_eval(batch, partition_id=partition_id)
            h = H.hash_columns(keys, seed=42, capacity=cap)
            return H.pmod(h, self.n)
        if self.mode == "range":
            return self._range_ids(batch, partition_id)
        raise ValueError(f"unknown partitioning mode {self.mode!r}")

    def _range_ids(self, batch: Batch, partition_id: int):
        from auron_tpu.ops.sort_keys import encode_sort_keys
        keys = self._key_eval(batch, partition_id=partition_id)
        words = encode_sort_keys(keys, self._orders)
        if self._bounds_words is None:
            self._bounds_words = encoded_range_bounds(
                self.part.range_bounds, self.part.sort_orders,
                self._orders)
        return range_ids_from_words(words, self._bounds_words,
                                    batch.capacity)


def range_ids_from_words(words, bounds, capacity: int):
    """Range partition ids from encoded sort-key words: id = count of
    bounds lexicographically < the row key (ties go to the lower
    partition).  Shared by the serial repartitioner and the SPMD stage
    tracer (parallel/stage.py) so the bound-compare semantics cannot
    drift.  `bounds` is the [n_bounds, n_words] uint64 matrix from
    encoded_range_bounds; num bounds = N-1, small."""
    ids = jnp.zeros(capacity, jnp.int32)
    for b in range(bounds.shape[0]):
        lt = jnp.zeros(capacity, bool)
        decided = jnp.zeros(capacity, bool)
        for wi, w in enumerate(words):
            bw = bounds[b, wi]
            is_lt = jnp.logical_and(jnp.logical_not(decided), w > bw)
            is_gt = jnp.logical_and(jnp.logical_not(decided), w < bw)
            lt = jnp.logical_or(lt, is_lt)
            decided = jnp.logical_or(decided, jnp.logical_or(is_lt, is_gt))
        ids = ids + lt.astype(jnp.int32)
    return ids


def encoded_range_bounds(range_bounds, sort_orders, orders):
    """Encode driver-sampled bound rows (tuples of python values) into
    the [n_bounds, n_words] uint64 sort-key-word matrix."""
    from auron_tpu.exprs.host_eval import HV
    from auron_tpu.ops.sort import _np_encode_key
    rows = range_bounds
    nb = len(rows)
    cols = list(zip(*rows)) if rows else []
    words: List[np.ndarray] = []
    for ki, s in enumerate(sort_orders):
        vals = np.array(cols[ki], dtype=object) if cols else \
            np.zeros(0, dtype=object)
        mask = np.array([v is not None for v in vals]) \
            if len(vals) else np.zeros(0, bool)
        dt = _python_dtype(vals, mask)
        safe = np.array([0 if (v is None or not m) else v
                         for v, m in zip(vals, mask)])
        hv = HV(safe if dt.is_stringlike is False else
                np.array([v if m else "" for v, m in
                          zip(vals, mask)], dtype=object),
                mask, dt)
        asc, nf = orders[ki]
        words.extend(_np_encode_key(hv, asc, nf))
    mat = np.stack(words, axis=1) if words else \
        np.zeros((nb, 0), np.uint64)
    return jnp.asarray(mat)


def _python_dtype(vals, mask):
    from auron_tpu.ir.schema import DataType
    for v, m in zip(vals, mask):
        if m and v is not None:
            if isinstance(v, bool):
                return DataType.bool_()
            if isinstance(v, (int, np.integer)):
                return DataType.int64()
            if isinstance(v, (float, np.floating)):
                return DataType.float64()
            if isinstance(v, str):
                return DataType.string()
    return DataType.int64()


def compute_partition_ids(part: Partitioning, schema: Schema, batch: Batch,
                          partition_id: int = 0, row_start: int = 0):
    return PartitionIdComputer(part, schema)(batch, partition_id, row_start)

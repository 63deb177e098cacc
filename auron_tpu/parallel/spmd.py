"""Fully-jitted SPMD query steps over a device mesh.

One compiled XLA program per stage shape: local expression kernels, hash
repartition over ICI all_to_all, sort-based local aggregation, broadcast
join probe via all_gather, global metrics via psum — the multi-chip
execution model of the framework (the dryrun_multichip entry exercises
exactly this path on a virtual mesh).
"""

from __future__ import annotations

from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from auron_tpu.exprs import hashing as H
from auron_tpu.ops import segments
from auron_tpu.parallel.exchange import (
    all_to_all_repartition, broadcast_all_gather, global_sum,
)
from auron_tpu.runtime import jitcheck


class QueryStepOut(NamedTuple):
    group_keys: Any      # [N, G] per-device aggregated keys (padded -1)
    group_sums: Any      # [N, G] float sums per key
    group_joined: Any    # [N, G] dim value joined onto each key
    group_count: Any     # [N, G] per-key row counts
    total_rows: Any      # [] global filtered row count (psum)


def make_query_step(mesh: Mesh, axis: str = "parts",
                    capacity: int = 1024):
    """Build the jitted SPMD step.

    Per-device inputs (sharded along `axis`):
      key   int64  [n_dev*C]  - group/join key
      amount f32   [n_dev*C]  - measure
      disc   f32   [n_dev*C]  - discount fraction
      valid  bool  [n_dev*C]  - live-row mask
    Replicated inputs:
      dim_key int64 [D], dim_val f32 [D] - small broadcast-joined table
    """
    if axis not in mesh.shape:
        raise ValueError(f"axis {axis!r} not in mesh axes {mesh.axis_names}")
    n_dev = mesh.shape[axis]
    quota = capacity

    def per_device(key, amount, disc, valid, dim_key_shard, dim_val_shard):
        # 1. filter: amount > 0 (data-dependent mask, static shapes)
        keep = jnp.logical_and(valid, amount > 0)
        # 2. project: net = amount * (1 - disc)
        net = jnp.where(keep, amount * (1.0 - disc), 0.0)
        # 3. hash repartition by key over ICI (spark murmur3 seed 42)
        kcol = _FakeCol(key, keep)
        h = H.hash_columns([kcol], seed=42)
        pid = H.pmod(h, n_dev)
        (rk, rnet), rvalid, _ovf = all_to_all_repartition(
            [key, net], pid, keep, axis, n_dev, quota)
        # 4. broadcast exchange: dim table arrives sharded; all_gather
        #    materializes the full build side on every device (the
        #    TorrentBroadcast/BHJ-build analogue riding ICI)
        (dim_key, dim_val), _ = broadcast_all_gather(
            [dim_key_shard, dim_val_shard],
            jnp.ones(dim_key_shard.shape[0], bool), axis)
        # 5. local sort-based aggregation + dim probe (shared kernel)
        gkeys, sums, joined, counts = local_group_aggregate(
            rk, rnet, rvalid, dim_key, dim_val)
        # 6. global metric over the mesh
        total = global_sum(jnp.sum(keep.astype(jnp.int64)), axis)
        return gkeys, sums, joined, counts, total

    shard = jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(PS(axis), PS(axis), PS(axis), PS(axis), PS(axis), PS(axis)),
        out_specs=(PS(axis), PS(axis), PS(axis), PS(axis), PS()),
        check_vma=False)

    def step(key, amount, disc, valid, dim_key, dim_val) -> QueryStepOut:
        g, s, j, c, t = shard(key, amount, disc, valid, dim_key, dim_val)
        return QueryStepOut(g, s, j, c, t)

    return jitcheck.site("spmd.query_step").jit(step)


def local_group_aggregate(key, value, live, dim_key, dim_val):
    """Shared local kernel: sort-based group-sum over (key, value) rows,
    then probe the (replicated) sorted dim table.  Used identically by the
    SPMD per-device body and the single-chip step."""
    cap2 = key.shape[0]
    sort_key = jnp.where(live, key, jnp.int64(2**62))
    # multi-operand sort carries the payload through the sorting network
    # instead of argsort + 3 gathers — gathers are the expensive part on
    # TPU (random-access HBM), the sort itself is MXU-adjacent vector work
    sk, sv, slive_i = jax.lax.sort(
        (sort_key, value, live.astype(jnp.int32)), num_keys=1,
        is_stable=False)
    slive = slive_i.astype(bool)
    boundary = jnp.logical_and(
        jnp.concatenate([jnp.ones(1, bool), sk[1:] != sk[:-1]]), slive)
    seg = segments.segment_bounds(
        jnp.where(slive, jnp.cumsum(boundary.astype(jnp.int32)) - 1,
                  cap2 - 1), cap2)
    sums = segments.sorted_segment_sum(jnp.where(slive, sv, 0.0), seg,
                                       cap2)
    counts = segments.sorted_segment_sum(slive.astype(jnp.int64), seg,
                                         cap2)
    first_idx = jnp.nonzero(boundary, size=cap2, fill_value=cap2 - 1)[0]
    gkeys = jnp.where(jnp.arange(cap2, dtype=jnp.int32) < jnp.sum(boundary),
                      jnp.take(sk, first_idx), -1)
    # stable: with duplicate dim keys, the first-occurring row must win
    # deterministically (searchsorted probes the leftmost equal slot)
    dk, dv = jax.lax.sort((dim_key, dim_val), num_keys=1, is_stable=True)
    pos = jnp.clip(jnp.searchsorted(dk, gkeys), 0, dk.shape[0] - 1)
    hit = jnp.take(dk, pos) == gkeys
    joined = jnp.where(hit, jnp.take(dv, pos), jnp.nan)
    return gkeys, sums, joined, counts


def make_single_chip_step():
    """The single-chip forward step: same pipeline minus collectives
    (filter -> project -> hash -> sort-based group-sum -> dim-table probe);
    sized entirely by its input shapes.  Used for compile checks and as the
    bench kernel."""

    def step(key, amount, disc, valid, dim_key, dim_val):
        keep = jnp.logical_and(valid, amount > 0)
        net = jnp.where(keep, amount * (1.0 - disc), 0.0)
        gkeys, sums, joined, counts = local_group_aggregate(
            key, net, keep, dim_key, dim_val)
        return gkeys, sums, joined, counts, jnp.sum(keep.astype(jnp.int64))

    return jitcheck.site("spmd.single_chip").jit(step)


class _FakeCol:
    """Minimal duck-typed column for hashing inside SPMD bodies."""

    def __init__(self, data, validity):
        self.data = data
        self.validity = validity
        from auron_tpu.ir.schema import DataType
        self.dtype = DataType.int64()


def example_inputs(mesh: Mesh, axis: str = "parts", capacity: int = 1024,
                   seed: int = 0, dim_rows: int = 64):
    """Sharded example inputs sized for the mesh (dim table is sharded too
    — the step all_gathers it, exercising the broadcast exchange)."""
    n_dev = mesh.shape[axis]
    rng = np.random.default_rng(seed)
    n = n_dev * capacity
    key = rng.integers(0, 50, n).astype(np.int64)
    amount = rng.normal(10, 5, n).astype(np.float32)
    disc = rng.uniform(0, 0.5, n).astype(np.float32)
    valid = np.ones(n, bool)
    dim_rows = ((dim_rows + n_dev - 1) // n_dev) * n_dev  # shardable
    dim_key = np.arange(dim_rows, dtype=np.int64)
    dim_val = rng.normal(0, 1, dim_rows).astype(np.float32)
    sharded = NamedSharding(mesh, PS(axis))
    put = lambda a, s: jax.device_put(a, s)  # noqa: E731
    return (put(key, sharded), put(amount, sharded), put(disc, sharded),
            put(valid, sharded), put(dim_key, sharded),
            put(dim_val, sharded))

"""Pallas TPU kernels — measured negative control.

Fused spark-murmur3 + pmod partition-id computation for the
single-int64-key hash repartition (reference semantics
shuffle/mod.rs:164-189, seed 42): the whole hash→pid chain in one VMEM
pass per row tile.

STATUS (round 3, by the numbers): this kernel measured 2.3x SLOWER than
the plain XLA elementwise chain on a real TPU v5e chip (BENCH_r03 kernel
profile: 0.061ms pallas vs 0.027ms xla at 4M rows — XLA already fuses
the hash chain optimally), so the production partitioner
(ops/shuffle/partitioner.py) no longer calls it.  It is retained ONLY as
the head-to-head baseline bench.py's worker_profile re-measures every
round, keeping the "Pallas where it pays" policy anchored to a live
number instead of an opinion.  The round-3 probe-kernel experiment
(vectorized binary search) is not expressible efficiently either: Mosaic
only lowers 2-D per-lane-column gathers, and XLA's searchsorted is
already near memory-bound (0.188ms / 4M probes).  The measured
conclusion: this engine's per-kernel device costs are micro-seconds and
XLA-fused; the optimization budget belongs to host orchestration, not
hand-written kernels.

TPU constraints honored:
- all arithmetic is uint32 (the VPU is 32-bit; int64 keys are bitcast to
  (lo, hi) u32 pairs before entering the kernel);
- rows are viewed as (rows/128, 128) lanes, gridded over row tiles;
- off-TPU the public entry falls back to the jnp implementation
  (exprs/hashing.py) — interpret mode is for tests only.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from auron_tpu.config import conf
# reuse the exact jnp murmur3 primitives — bit-parity between this kernel
# and the fallback path is load-bearing (supported() picks per batch)
from auron_tpu.exprs.hashing import _fmix, _mix_h1, _mix_k1
from auron_tpu.runtime import jitcheck

_SEED = np.uint32(42)

_LANES = 128
_MAX_TILE_ROWS = 256  # (256, 128) u32 tiles: 128KB/input in VMEM


def _pid_kernel(lo_ref, hi_ref, valid_ref, out_ref, *, n_parts: int):
    lo = lo_ref[:]
    hi = hi_ref[:]
    v = valid_ref[:]
    h = _mix_h1(jnp.full_like(lo, _SEED), _mix_k1(lo))
    h = _mix_h1(h, _mix_k1(hi))
    h = _fmix(h, 8)
    # null key: hash stays the seed (spark skips null columns)
    h = jnp.where(v != 0, h, jnp.full_like(h, _SEED))
    hs = h.astype(jnp.int32)
    # jnp % on int32 is floor-mod => already non-negative for n_parts > 0
    out_ref[:] = hs % np.int32(n_parts)


def supported(keys, platform: str | None = None) -> bool:
    """Is the pallas fast path applicable to these evaluated key columns?"""
    if not bool(conf.get("auron.pallas.enable")):
        return False
    platform = platform or jax.default_backend()
    if platform != "tpu":
        return False
    if len(keys) != 1:
        return False
    c = keys[0]
    from auron_tpu.columnar.batch import DeviceColumn
    if not isinstance(c, DeviceColumn):
        return False
    from auron_tpu.ir.schema import TypeId
    if c.dtype.id not in (TypeId.INT64, TypeId.TIMESTAMP_US):
        return False
    return c.data.shape[0] % _LANES == 0


# jit-site wrap happens at import: the env fallback must be set at
# process start for these module-level kernels to be probed (conftest)
@functools.partial(jitcheck.site("pallas.hash_pid").jit,
                   static_argnames=("n_parts", "interpret"))
def hash_partition_ids_i64(data, validity, n_parts: int,
                           interpret: bool = False):
    """pid = pmod(murmur3_spark(int64 key, seed=42), n_parts) as one pallas
    pass.  data: int64[cap] (cap % 128 == 0), validity: bool[cap]."""
    cap = data.shape[0]
    rows = cap // _LANES
    tile_rows = min(rows, _MAX_TILE_ROWS)
    while rows % tile_rows:
        tile_rows -= 1
    v64 = data.astype(jnp.uint64)
    lo = (v64 & np.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    hi = (v64 >> np.uint64(32)).astype(jnp.uint32)
    lo2 = lo.reshape(rows, _LANES)
    hi2 = hi.reshape(rows, _LANES)
    va2 = validity.astype(jnp.uint32).reshape(rows, _LANES)
    grid = (rows // tile_rows,)
    spec = pl.BlockSpec((tile_rows, _LANES), lambda i: (i, 0))
    # mosaic rejects i64 index/iota types: trace the kernel in 32-bit mode
    # (the engine enables x64 globally; all kernel operands are 32-bit)
    with jax.enable_x64(False):
        out = pl.pallas_call(
            functools.partial(_pid_kernel, n_parts=n_parts),
            out_shape=jax.ShapeDtypeStruct((rows, _LANES), jnp.int32),
            grid=grid,
            in_specs=[spec, spec, spec],
            out_specs=spec,
            interpret=interpret,
        )(lo2, hi2, va2)
    return out.reshape(cap)


# ---------------------------------------------------------------------------
# radix-partition staging kernel (the TPU half of the pack-sort strategy)
# ---------------------------------------------------------------------------
#
# The CPU radix strategy (ops/radix_sort.py) rides XLA's value sort; on a
# real TPU the equivalent partition pass is a per-tile bucket HISTOGRAM
# (digit extract + count) that a stitch pass turns into scatter offsets.
# This kernel is that histogram, fused into one VMEM pass per row tile —
# staged here under the module's measured-negative-control policy: the
# bench profile can head-to-head it against the XLA twin on a chip before
# any production path adopts it (the round-3 lesson: the hash-pid pallas
# kernel LOST 2.3x to XLA's fusion; numbers first).

_HIST_MAX_BUCKETS = 256


def _radix_hist_kernel(hi_ref, out_ref, *, b_bits: int):
    hi = hi_ref[:]
    digit = (hi >> np.uint32(32 - b_bits)).astype(jnp.int32)
    # B is small and static: the bucket loop unrolls into B vector
    # compare+reduce chains over the tile — pure VPU work, no scatter.
    # The counts are assembled into ONE lane vector and stored once
    # (Mosaic has no scalar store into a VMEM block), and each count
    # is reduced rows-then-lanes down to a (1, 1) vector: a reduction
    # over ALL axes is re-traced by Mosaic at lowering time, outside
    # this kernel's 32-bit scope, where the engine's global x64 widens
    # it to an int64 Mosaic refuses.
    n_buckets = 1 << b_bits
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, n_buckets), 1)
    hist = jnp.zeros((1, n_buckets), jnp.int32)
    for b in range(n_buckets):
        per_lane = jnp.sum((digit == b).astype(jnp.int32), axis=0,
                           keepdims=True)
        count = jnp.sum(per_lane, axis=1, keepdims=True)
        hist = jnp.where(lane == b, count, hist)
    out_ref[0] = hist


def radix_bucket_hist_xla(hi, b_bits: int, tile_rows: int = _MAX_TILE_ROWS):
    """jnp reference twin: per-tile bucket histogram of the u32 key high
    word, [n_tiles, 2^b_bits] (tile = tile_rows*128 keys)."""
    digit = (hi.astype(jnp.uint32) >> np.uint32(32 - b_bits)) \
        .astype(jnp.int32)
    tiles = digit.reshape(-1, tile_rows * _LANES)
    gids = jnp.arange(1 << b_bits, dtype=jnp.int32)
    return jnp.sum((tiles[:, :, None] == gids[None, None, :])
                   .astype(jnp.int32), axis=1)


@functools.partial(jitcheck.site("pallas.radix_hist").jit,
                   static_argnames=("b_bits", "interpret"))
def radix_bucket_hist(hi, b_bits: int, interpret: bool = False):
    """Per-tile radix bucket histogram as one pallas pass.  hi:
    uint32[cap] key high words, cap % (tile_rows*128) == 0; returns
    int32[n_tiles, 2^b_bits]."""
    if not 1 <= (1 << b_bits) <= _HIST_MAX_BUCKETS:
        raise ValueError(f"b_bits {b_bits} outside staging range")
    cap = hi.shape[0]
    rows = cap // _LANES
    tile_rows = min(rows, _MAX_TILE_ROWS)
    while rows % tile_rows:
        tile_rows -= 1
    hi2 = hi.astype(jnp.uint32).reshape(rows, _LANES)
    grid = (rows // tile_rows,)
    # one (1, B) histogram row per tile, kept as the LAST TWO dims of a
    # 3-D output: Mosaic wants a block's last two dims (8, 128)-aligned
    # or equal to the array's, and a (1, B) block of an (n_tiles, B)
    # array is neither
    n_buckets = 1 << b_bits
    with jax.enable_x64(False):
        out = pl.pallas_call(
            functools.partial(_radix_hist_kernel, b_bits=b_bits),
            out_shape=jax.ShapeDtypeStruct(
                (rows // tile_rows, 1, n_buckets), jnp.int32),
            grid=grid,
            in_specs=[pl.BlockSpec((tile_rows, _LANES), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((1, 1, n_buckets),
                                   lambda i: (i, 0, 0)),
            interpret=interpret,
        )(hi2)
    return out.reshape(rows // tile_rows, n_buckets)

"""Spark-compatible hashes as device kernels.

The exchange layer computes partition ids on device:
pid = pmod(murmur3_hash(keys, seed=42), num_partitions) — exactly the
reference's shuffle semantics (native-engine/datafusion-ext-plans/src/
shuffle/mod.rs:164-189, spark_hash.rs), so a mixed deployment (this engine
for some stages, Spark for others) shuffles identically.

Per-type Spark encoding (Murmur3_x86_32):
- int8/16/32/bool/date32 -> hashInt(v as i32)
- int64/timestamp        -> hashLong (two 4-byte blocks, len=8 finalize)
- float32 -> hashInt(bits), float64 -> hashLong(bits); -0.0 normalized
- decimal(p<=18) -> hashLong(unscaled)
- string/binary -> hashUnsafeBytes (4-byte LE blocks + signed tail bytes)

All arithmetic is uint32/int32 on device (no 64-bit mults on the hot path);
xxhash64 (Spark's XxHash64 expression) uses uint64 ops via jax x64.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from auron_tpu.columnar.batch import DeviceColumn, DeviceStringColumn
from auron_tpu.ir.schema import TypeId

_C1 = np.uint32(0xCC9E2D51)
_C2 = np.uint32(0x1B873593)


def _rotl32(x, r: int):
    return (x << r) | (x >> (32 - r))


def _mix_k1(k1):
    k1 = k1 * _C1
    k1 = _rotl32(k1, 15)
    return k1 * _C2


def _mix_h1(h1, k1):
    h1 = h1 ^ k1
    h1 = _rotl32(h1, 13)
    return h1 * np.uint32(5) + np.uint32(0xE6546B64)


def _fmix(h1, length):
    h1 = h1 ^ jnp.uint32(length)
    h1 = h1 ^ (h1 >> 16)
    h1 = h1 * np.uint32(0x85EBCA6B)
    h1 = h1 ^ (h1 >> 13)
    h1 = h1 * np.uint32(0xC2B2AE35)
    return h1 ^ (h1 >> 16)


def hash_int32(v, seed):
    """v: int32 array; seed: uint32 array or scalar -> uint32."""
    k1 = _mix_k1(v.astype(jnp.uint32))
    h1 = _mix_h1(jnp.asarray(seed, jnp.uint32), k1)
    return _fmix(h1, 4)


def hash_int64(v, seed):
    v = v.astype(jnp.int64)
    lo = (v & 0xFFFFFFFF).astype(jnp.uint32)
    hi = ((v >> 32) & 0xFFFFFFFF).astype(jnp.uint32)
    h1 = _mix_h1(jnp.asarray(seed, jnp.uint32), _mix_k1(lo))
    h1 = _mix_h1(h1, _mix_k1(hi))
    return _fmix(h1, 8)


def hash_float32(v, seed):
    v = jnp.where(v == 0.0, 0.0, v)  # -0.0 -> 0.0
    bits = jax_bitcast_i32(v.astype(jnp.float32))
    return hash_int32(bits, seed)


def hash_float64(v, seed):
    v = jnp.where(v == 0.0, 0.0, v)
    lo, hi = f64_bits_u32_pair(v)
    h1 = _mix_h1(jnp.asarray(seed, jnp.uint32), _mix_k1(lo))
    h1 = _mix_h1(h1, _mix_k1(hi))
    return _fmix(h1, 8)


def hash_f64_bits(bits, seed):
    """hash_float64 from exact uint64 IEEE bits (the DeviceColumn.bits
    sidecar): Spark-exact double hashing even where f64 is demoted.
    Normalizes -0.0 like the value path."""
    bits = jnp.where(bits == jnp.uint64(0x8000000000000000),
                     jnp.uint64(0), bits)
    lo = (bits & jnp.uint64(0xFFFFFFFF)).astype(jnp.uint32)
    hi = (bits >> 32).astype(jnp.uint32)
    h1 = _mix_h1(jnp.asarray(seed, jnp.uint32), _mix_k1(lo))
    h1 = _mix_h1(h1, _mix_k1(hi))
    return _fmix(h1, 8)


def jax_bitcast_i32(v):
    import jax.lax as lax
    return lax.bitcast_convert_type(v, jnp.int32)


def f64_bits_u32_pair(v):
    """(lo, hi) uint32 words of the IEEE-754 double bits.

    TPU CAVEAT: XLA's x64 rewrite pass does not implement 64-bit
    bitcast-convert, and f64 itself is demoted on TPU — so on TPU backends
    the value is hashed through its float32 bits (hi word = 0).  This keeps
    partitioning internally consistent across an all-TPU mesh; bit-exact
    Spark parity for double hashing holds on CPU/GPU backends.
    """
    import jax
    import jax.lax as lax
    # by backend because it is a capability, not an alternative
    if jax.default_backend() == "cpu" or jax.default_backend() == "gpu":
        pair = lax.bitcast_convert_type(v.astype(jnp.float64), jnp.uint32)
        return pair[..., 0], pair[..., 1]
    bits32 = lax.bitcast_convert_type(v.astype(jnp.float32), jnp.uint32)
    return bits32, jnp.zeros_like(bits32)


def hash_bytes(data, lengths, seed):
    """Spark hashUnsafeBytes over padded byte matrices.

    data: uint8[rows, W] zero-padded, lengths: int32[rows].  Processes
    len//4 4-byte LE blocks then tail bytes individually (as *signed*
    int8).  W is static, so the loop unrolls into W/4 fused mixes with
    per-row masking — each row applies exactly the mixes its length needs
    by carrying an h state per prefix and selecting.
    """
    rows, w = data.shape
    seed = jnp.broadcast_to(jnp.asarray(seed, jnp.uint32), (rows,))
    nblocks = lengths // 4
    # cast per byte-column slice, NOT the whole [rows, w] array: the
    # full u32 cast is a 4x temp XLA keeps live across every block use
    # (same sf10 OOM family as encode_key_column's u64 cast)
    def d32(i):
        return data[:, i].astype(jnp.uint32)
    h = seed
    # full 4-byte blocks: iterate static W//4 positions, masked per row
    for b in range(w // 4):
        k = (d32(4 * b) | (d32(4 * b + 1) << 8)
             | (d32(4 * b + 2) << 16) | (d32(4 * b + 3) << 24))
        nh = _mix_h1(h, _mix_k1(k))
        h = jnp.where(b < nblocks, nh, h)
    # tail bytes (signed), one at a time
    for t in range(min(3, w)):
        byte_idx = nblocks * 4 + t
        in_tail = byte_idx < lengths
        raw = jnp.take_along_axis(data, jnp.clip(byte_idx, 0, w - 1)[:, None],
                                  axis=1)[:, 0]
        signed = raw.astype(jnp.int8).astype(jnp.int32).astype(jnp.uint32)
        nh = _mix_h1(h, _mix_k1(signed))
        h = jnp.where(in_tail, nh, h)
    return _fmix(h, lengths.astype(jnp.uint32))


def _hash_host_column(col, seed):  # jitcheck: waive (HostColumn arm: hash_columns dispatches here only for host-resident columns, which the jitted paths exclude upstream)
    """Host-resident rows (oversized strings, hybrid batches): Spark
    murmur3 computed on host (spark_hash.rs StringType/BinaryType arm);
    null and padding rows keep the incoming per-row seed."""
    import decimal as _dec
    from auron_tpu.exprs.host_eval import decimal_unscaled
    from auron_tpu.native import bindings
    seeds = np.asarray(seed, dtype=np.uint32)
    out = seeds.copy()
    for i, v in enumerate(col.pylist()):
        if v is None:
            continue
        if isinstance(v, str):
            b = v.encode("utf-8")
        elif isinstance(v, bytes):
            b = v
        elif isinstance(v, _dec.Decimal):
            # Spark DecimalType p>18: murmur3 over the java BigDecimal
            # unscaledValue().toByteArray() — minimal big-endian two's
            # complement (spark_hash.rs decimal arm).  Java bitLength
            # excludes the sign bit: bitLength(-2^k) == k, so negatives
            # use (-v-1).bit_length()
            unscaled = decimal_unscaled(v, col.dtype.scale)
            bl = (-unscaled - 1).bit_length() if unscaled < 0 \
                else unscaled.bit_length()
            b = unscaled.to_bytes(bl // 8 + 1, "big", signed=True)
        else:
            raise TypeError(
                f"unhashable host value {type(v).__name__} ({col.dtype})")
        out[i] = np.uint32(
            bindings.murmur3_32(b, int(seeds[i].astype(np.int32)))
            & 0xFFFFFFFF)
    return jnp.asarray(out)


def hash_column(col, seed):
    """Dispatch per logical type -> uint32 hash; null rows keep the incoming
    seed unchanged (Spark semantics: nulls don't contribute)."""
    from auron_tpu.columnar.batch import HostColumn
    seed = jnp.asarray(seed, jnp.uint32)
    if isinstance(col, HostColumn):
        return _hash_host_column(col, seed)
    if isinstance(col, DeviceStringColumn):
        h = hash_bytes(col.data, col.lengths, seed)
    else:
        tid = col.dtype.id
        if tid in (TypeId.BOOL,):
            h = hash_int32(col.data.astype(jnp.int32), seed)
        elif tid in (TypeId.INT8, TypeId.INT16, TypeId.INT32, TypeId.DATE32):
            h = hash_int32(col.data.astype(jnp.int32), seed)
        elif tid in (TypeId.INT64, TypeId.TIMESTAMP_US, TypeId.DECIMAL):
            h = hash_int64(col.data, seed)
        elif tid == TypeId.FLOAT32:
            h = hash_float32(col.data, seed)
        elif tid == TypeId.FLOAT64:
            from auron_tpu.ops.sort_keys import (f64_bits_of_column,
                                                 f64_exact_bits_enabled)
            if f64_exact_bits_enabled():
                # ALL f64 hashing goes through the bits space when the
                # sidecar is live (ingested: exact; computed: widened from
                # the f32-exact stored value) — mixing bit-exact and
                # f32-granular hashes for the same value would route join/
                # shuffle sides to different partitions
                h = hash_f64_bits(f64_bits_of_column(col), seed)
            else:
                h = hash_float64(col.data, seed)
        else:
            raise TypeError(f"unhashable device type {col.dtype}")
    bseed = jnp.broadcast_to(seed, h.shape)
    return jnp.where(col.validity, h, bseed)


def hash_columns(cols, seed=42, capacity=None):
    """Chained multi-column hash (each column's hash seeds the next),
    Spark HashExpression semantics; returns int32.  `capacity` pads the
    seed vector when host columns (unpadded) are narrower than the owning
    batch."""
    cap = capacity
    if cap is None:
        cap = max(c.capacity if hasattr(c, "capacity")
                  else c.data.shape[0] for c in cols)
    h = jnp.full(cap, np.uint32(seed), jnp.uint32)
    for c in cols:
        h = hash_column(c, h)
    return h.astype(jnp.int32)


def pmod(x, m: int):
    """Positive modulo (partition id from hash)."""
    r = x % jnp.int32(m)
    return jnp.where(r < 0, r + m, r)


# ---------------------------------------------------------------------------
# xxhash64 (Spark XxHash64 expression; shuffle checksums)
# ---------------------------------------------------------------------------

_XP1 = np.uint64(0x9E3779B185EBCA87)
_XP2 = np.uint64(0xC2B2AE3D27D4EB4F)
_XP3 = np.uint64(0x165667B19E3779F9)
_XP4 = np.uint64(0x85EBCA77C2B2AE63)
_XP5 = np.uint64(0x27D4EB2F165667C5)


def _xrotl(x, r: int):
    return (x << r) | (x >> (64 - r))


def xxh64_int64(v, seed):
    """xxhash64 of a single 8-byte value (Spark XxHash64 on longs)."""
    v = v.astype(jnp.uint64)
    seed = jnp.asarray(seed, jnp.uint64)
    h = seed + _XP5 + jnp.uint64(8)
    k = _xrotl(v * _XP2, 31) * _XP1
    h = h ^ k
    h = _xrotl(h, 27) * _XP1 + _XP4
    h = h ^ (h >> 33)
    h = h * _XP2
    h = h ^ (h >> 29)
    h = h * _XP3
    return h ^ (h >> 32)

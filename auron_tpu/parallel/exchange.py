"""Collective exchange kernels (called INSIDE shard_map bodies).

The shuffle redesign: where the reference writes per-partition sorted runs
to files fetched by the next stage (sort_repartitioner.rs + Spark block
store), an SPMD stage reshuffles rows in-flight with lax.all_to_all.

Shapes must be static, so the exchange uses a fixed per-destination quota
Q: each device scatters its rows into an [N, Q] send buffer grouped by
destination, all_to_all swaps blocks, and receivers compact the valid rows.

Quota sizing (round-3 fix: quota=capacity made every post-exchange buffer
GLOBAL sized, nullifying memory scaling): hash/round-robin exchanges use a
skew-margined per-destination quota ~ capacity/n_dev * margin, so the
received buffer is O(global/n_dev * margin); a single-partition exchange
keeps Q = capacity (one device legitimately receives everything).  Rows
beyond quota cannot be silently lost: every exchange returns an `overflow`
device flag that callers must surface (the SPMD stage compiler psums it
into its runtime guards, and the driver falls back to the serial engine —
the same escape hatch the reference's sort-based repartitioner never
needs because its buffers are dynamic, buffered_data.rs:285).
"""

from __future__ import annotations

from typing import Any, List, Tuple

import jax
import jax.numpy as jnp
from jax import lax


def bounded_quota(capacity: int, n_dev: int,
                  margin: float | None = None) -> int:
    """Skew-margined per-destination quota for hash/round-robin exchanges:
    ceil(capacity / n_dev) * margin, rounded up to a multiple of 8.  The
    received buffer is then n_dev * quota ~= capacity * margin instead of
    n_dev * capacity."""
    if margin is None:
        from auron_tpu.config import conf
        margin = float(conf.get("auron.spmd.exchange.quota.margin"))
    per = -(-capacity // max(n_dev, 1))
    q = int(per * margin) + 8
    return min(capacity, -(-q // 8) * 8)


def _scatter_to_send(data, dest, valid, n_dev: int, quota: int):
    """data: [C, ...] row-major payload; dest int32 [C]; -> [N, Q, ...]."""
    cap = dest.shape[0]
    safe_dest = jnp.where(valid, dest, n_dev)          # invalid -> dropped
    # within-destination slot: stable rank of each row among its dest
    # group
    from auron_tpu.ops.sort_keys import stable_argsort
    order = stable_argsort(safe_dest)                  # groups by dest
    sorted_dest = jnp.take(safe_dest, order)
    idx = jnp.arange(cap, dtype=jnp.int32)
    # start offset of each dest group in sorted order
    is_start = jnp.concatenate([jnp.ones(1, bool),
                                sorted_dest[1:] != sorted_dest[:-1]])
    group_start = lax.cummax(jnp.where(is_start, idx, -1))
    slot_sorted = idx - group_start
    # scatter into [N*Q] flat send buffer
    flat_pos = sorted_dest * quota + jnp.minimum(slot_sorted, quota - 1)
    ok = jnp.logical_and(sorted_dest < n_dev, slot_sorted < quota)
    flat_pos = jnp.where(ok, flat_pos, n_dev * quota)  # spill to scratch row
    payload = jnp.take(data, order, axis=0)
    out_shape = (n_dev * quota + quota,) + data.shape[1:]
    send = jnp.zeros(out_shape, data.dtype)
    send = send.at[flat_pos].set(payload, mode="drop")
    send_valid = jnp.zeros(n_dev * quota + quota, bool)
    send_valid = send_valid.at[flat_pos].set(ok, mode="drop")
    send = send[:n_dev * quota].reshape((n_dev, quota) + data.shape[1:])
    send_valid = send_valid[:n_dev * quota].reshape(n_dev, quota)
    # a valid row routed to a real destination but past its quota slot was
    # dropped from the buffer — flag it (callers must not ignore this)
    overflow = jnp.any(jnp.logical_and(
        jnp.logical_and(sorted_dest < n_dev, slot_sorted >= quota),
        jnp.take(valid, order)))
    return send, send_valid, overflow


def destination_counts(dest, valid, n_dev: int):
    """int32 [N]: how many valid rows of this device go to each destination
    — the rows of its send buffer's blocks before the quota cuts them."""
    to = dest[None, :] == jnp.arange(n_dev, dtype=dest.dtype)[:, None]
    return jnp.sum(jnp.logical_and(to, valid[None, :]), axis=1,
                   dtype=jnp.int32)


def all_to_all_repartition(arrays: List[Any], dest, valid, axis: str,
                           n_dev: int, quota: int
                           ) -> Tuple[List[Any], Any, Any]:
    """Repartition rows of `arrays` (each [C, ...]) by `dest` device ids.

    Returns (received_arrays each [N*Q, ...], received_valid [N*Q],
    overflow bool scalar — LOCAL to this device; psum/any-reduce it).
    Must run inside shard_map with named axis `axis`.  The send buffers'
    construction traces under the scope `scatter`, the transfer under
    `all_to_all`.
    """
    outs = []
    recv_valid = None
    overflow = None
    for a in arrays:
        with jax.named_scope("scatter"):
            send, send_valid, ovf = _scatter_to_send(a, dest, valid, n_dev,
                                                     quota)
        with jax.named_scope("all_to_all"):
            recv = lax.all_to_all(send, axis, split_axis=0, concat_axis=0,
                                  tiled=False)
            outs.append(recv.reshape((n_dev * quota,) + a.shape[1:]))
            if recv_valid is None:
                overflow = ovf
                rv = lax.all_to_all(send_valid, axis, split_axis=0,
                                    concat_axis=0, tiled=False)
                recv_valid = rv.reshape(n_dev * quota)
    if overflow is None:
        overflow = jnp.asarray(False)
    return outs, recv_valid, overflow


def broadcast_all_gather(arrays: List[Any], valid, axis: str
                         ) -> Tuple[List[Any], Any]:
    """Broadcast exchange: every device receives every device's rows
    (the BHJ build-side path: one all_gather instead of TorrentBroadcast).
    arrays: [C, ...] -> [N*C, ...]."""
    outs = []
    for a in arrays:
        g = lax.all_gather(a, axis, axis=0, tiled=False)
        outs.append(g.reshape((-1,) + a.shape[1:]))
    gv = lax.all_gather(valid, axis, axis=0, tiled=False).reshape(-1)
    return outs, gv


def global_sum(x, axis: str):
    return lax.psum(x, axis)


def hierarchical_repartition(arrays: List[Any], dest, valid,
                             ici_axis: str, dcn_axis: str,
                             n_ici: int, n_dcn: int, quota: int,
                             bound_stage2: bool = True):
    """Two-stage repartition for multi-slice meshes: rows first move
    WITHIN a slice (over the fast ICI axis) to the local chip whose ICI
    rank matches the destination chip, then cross slices over DCN in one
    aligned all_to_all.

    This is the standard hierarchical all-to-all: every row crosses DCN at
    most once and the DCN transfer is slice-to-slice aligned, instead of a
    flat all_to_all over N_ici*N_dcn devices whose traffic is dominated by
    the slow axis (SURVEY §2.5: "lay out shardings so collectives ride
    ICI, not DCN").

    `dest` is the GLOBAL destination device id laid out as
    dcn_rank * n_ici + ici_rank.  `quota` is the per-destination bound of
    stage 1, which spreads over the n_ici LOCAL chips — size it for
    n_ici destinations (bounded_quota(capacity, n_ici)), not n_dev.
    Must run inside shard_map with both named axes.  Returns
    ([n_dcn*q2, ...] arrays, valid mask, overflow flag) on each
    destination device, where q2 = n_ici*quota unbounded, or its
    n_dcn-margined bound when bound_stage2 (same row-layout contract as
    all_to_all_repartition).
    """
    # stage 1 (ICI): deliver each row to the local chip with ici_rank ==
    # dest_ici; rows keep their dcn destination as payload
    dest_ici = (dest % n_ici).astype(jnp.int32)
    dest_dcn = (dest // n_ici).astype(jnp.int32)
    stage1, v1, ovf1 = all_to_all_repartition(
        arrays + [dest_dcn], dest_ici, valid, ici_axis, n_ici, quota)
    payload1, dcn1 = stage1[:-1], stage1[-1]
    # stage 2 (DCN): every chip now holds only rows whose final chip has
    # its own ici_rank; swap across slices by dcn rank.  Stage-1 output
    # splits over n_dcn destinations, so the same margined bound applies
    # (n_ici*quota covers the worst case; the bound keeps receive buffers
    # O(global/n_dev))
    cap1 = n_ici * quota
    q2 = cap1 if (n_dcn <= 1 or not bound_stage2) \
        else min(cap1, bounded_quota(cap1, n_dcn))
    stage2, v2, ovf2 = all_to_all_repartition(
        payload1, dcn1, v1, dcn_axis, n_dcn, q2)
    return stage2, v2, jnp.logical_or(ovf1, ovf2)

"""SPMD stage compiler: planner IR -> ONE jitted shard_map program.

This is the multi-chip execution path of the engine (SURVEY §2.5 rows
67-72; reference analogue: Spark schedules the reference's native tasks
per partition, rt.rs:76-139, with shuffle files between stages,
shuffle/mod.rs:112-189).  On TPU the whole pipeline compiles to one XLA
program over a `jax.sharding.Mesh`:

- partition (data) parallelism: every operator body runs per device on its
  shard of rows, shapes static, a `live` row mask carrying filtered-ness
  (no compaction between operators — the mask IS the selection vector);
- hash/round-robin/single repartitioning: murmur3(seed=42) partition ids
  computed on device, rows exchanged with `lax.all_to_all` riding ICI
  (parallel/exchange.py), replacing the reference's sort-based shuffle
  files;
- broadcast exchange: `lax.all_gather` materializes the build side on
  every device (NativeBroadcastExchangeBase.collectNative analogue);
- group aggregation: the same sort-based `_group_reduce_body` kernel the
  serial engine uses, traced inline — on the table it is given, or on its
  live rows compacted to the narrowest of a short ladder of widths that
  holds them: the capacity the output is cut to and two rungs below it
  (`_do_agg`: chosen in the program from the live count, per device, like
  the probe below);
- broadcast/hash/sort-merge join: one lookup contract, (build row, found)
  per probe row, computed one of two ways.  A single integer or date key
  whose live build keys span less than the build side's capacity probes a
  direct-address table (`table[key - min]`: one scatter to build, one
  gather to probe); the program decides that from the keys it sees
  (`lax.cond`, per device), with no option and no retry.  Everything else
  — strings, decimals, composite keys, sparse ids — sorts the build
  side's u64 key hashes and binary-searches them.  Duplicate build keys
  under a pair-emitting join trip a retryable guard and the driver
  re-traces with K-way pair expansion (double `searchsorted`); past the
  factor the plan falls back to the serial engine.  In a chain of inner
  joins over a source the later joins probe only the rows the first one
  left, compacted to the narrowest of a short ladder of widths that holds
  them (`_join_chain`: chosen in the program, per device, like the rest).

Anything the compiler cannot express raises `SpmdUnsupported`; callers
(AuronSession.execute with a mesh) fall back to the per-partition serial
path, mirroring how the reference falls back to JVM execution for
unconvertible plan sections (AuronConvertStrategy).
"""

from __future__ import annotations

import contextlib
import dataclasses
from dataclasses import dataclass
from typing import (
    Any, Callable, Collection, Dict, List, Optional, Tuple,
)

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as PS

from auron_tpu.columnar.batch import (
    DeviceColumn, DeviceDecimal128Column, DeviceStringColumn, HostColumn,
    bucket_capacity,
)
from auron_tpu.exprs import hashing as H
from auron_tpu.exprs.compiler import EvalCtx, device_capable, evaluate
from auron_tpu.ir import plan as P
from auron_tpu.ir.expr import Expr
from auron_tpu.ir.node import Node
from auron_tpu.ir.schema import DataType, Field, Schema, TypeId
from auron_tpu.ops import segments
from auron_tpu.ops.segments import inside_branch
from auron_tpu.ops.sort_keys import stable_argsort
from auron_tpu.parallel.exchange import (
    all_to_all_repartition, bounded_quota, broadcast_all_gather,
    destination_counts, hierarchical_repartition,
)
from auron_tpu.runtime import jitcheck

Array = Any


class SpmdUnsupported(Exception):
    """Plan shape the SPMD compiler cannot express; fall back to the
    serial per-partition engine."""


class SpmdGuardTripped(SpmdUnsupported):
    """A runtime guard invalidated the SPMD result.  `retryable` marks
    join duplicate-key trips a pair-expansion retry can fix; `shrink`
    marks agg capacity-shrink overflows the capacity LADDER retries
    (4x per step, then shrink off); `join_compact` marks join-chain
    compaction overflows a compaction-off retry fixes; hard trips
    (exchange quota overflow, dup keys past the factor or under a
    semi-like join) fall straight back to the serial engine."""

    def __init__(self, message: str, retryable: bool = False,
                 shrink: bool = False, join_compact: bool = False,
                 hard: bool = False):
        super().__init__(message)
        self.retryable = retryable
        self.shrink = shrink
        # the join-chain compaction overflowed: retry with compaction
        # disabled (independent of the agg shrink dimension)
        self.join_compact = join_compact
        # hard quota/dup-key trip: normally falls straight back to
        # serial, EXCEPT that while the agg capacity shrink is active the
        # downstream exchange quotas were sized from the SHRUNK capacity,
        # so a skewed routing that fit pre-shrink can overflow them — the
        # ladder gives such trips shrink climbs while cap_eff > 0 before
        # conceding (ADVICE r4)
        self.hard = hard


@dataclass
class DeviceTable:
    """Per-device value flowing between traced operator bodies."""
    schema: Schema
    cols: List[Any]     # DeviceColumn / DeviceStringColumn (capacity rows)
    live: Array         # bool[capacity]

    @property
    def capacity(self) -> int:
        return int(self.live.shape[0])


def _live_first_perm(live: Array) -> Array:
    """The stable permutation that brings live rows to the front (int32
    row numbers): join-chain compaction and the compact gather both cut
    or fetch a prefix of it."""
    return stable_argsort(jnp.logical_not(live))


def _compact_front(t: DeviceTable, n_live, new_cap: int) -> DeviceTable:
    """The live rows of `t`, in their order, as the front of a
    `new_cap`-row table; for a table with no more than `new_cap` of
    them (a row past it would be dropped).  A running count of the
    live mask gives each live row its destination, one scatter of row
    numbers gives the first `new_cap` entries of the live-first
    permutation, and every column is gathered once by those: the cost
    follows `new_cap`, not `t`'s capacity — no sort of the flags
    (`_live_first_perm`).  Stable, because `first`-like states and
    per-device limit prefixes read the rows' order."""
    with jax.named_scope("compact"):
        cap = t.capacity
        dest = jnp.cumsum(t.live.astype(jnp.int32)) - 1
        perm = jnp.zeros(new_cap, jnp.int32).at[
            jnp.where(t.live, dest, new_cap)
        ].set(jnp.arange(cap, dtype=jnp.int32), mode="drop")
        ok = jnp.arange(new_cap, dtype=jnp.int32) < n_live
        cols = [c.gather(perm, ok) for c in t.cols]
        return DeviceTable(t.schema, cols, ok)


# an aggregate's rungs end under this many rows: every rung is one more
# body, and a distinct sort takes 34 s and more to compile for a v5e from
# 2^17 rows on, 7 s at 2^14 (PR 22, tests/test_tpu_compile.py)
_RUNG_ROWS_END = 1 << 17


def _agg_rungs(new_cap: int, capacity: int) -> List[int]:
    """The widths below `new_cap` an aggregate over a `capacity`-row
    input may run at, ascending: a fourth and a thirty-second of
    `new_cap` (65,536 and 8,192 rows at the hint's 262,144; 32,768 alone
    at its first climb, 1,048,576), each a capacity bucket like every
    other, and each only where it is narrower than the input and under
    `_RUNG_ROWS_END`."""
    end = min(new_cap, capacity, _RUNG_ROWS_END)
    return sorted({r for r in (bucket_capacity(new_cap // 32),
                               bucket_capacity(new_cap // 4)) if r < end})


# a join chain's joins probe one another through these operators alone
_CHAIN_LINKS = (P.Projection, P.Filter)
# and its first join probes one of these: a source whose buffer is sized
# by its whole table, so the first join's selectivity is not bounded there
_CHAIN_SOURCES = (P.ParquetScan, P.OrcScan, P.FFIReader)


def _below_links(node):
    while isinstance(node, _CHAIN_LINKS):
        node = node.child
    return node


def join_chain(top) -> List[P.BroadcastJoin]:
    """The joins of the chain `top` ends, `top` first and the chain's first
    join last: inner broadcast joins, each probing the output of the one
    below it through projections and filters alone, the first of them
    probing a source (`_CHAIN_SOURCES`) through projections and filters
    alone.  Empty where `top` ends no chain of two joins or more: a left,
    semi or anti join breaks a chain, and so does a probe side that
    reaches an aggregate or an exchange."""
    joins = []
    node = top
    while isinstance(node, P.BroadcastJoin) and \
            node.join_type == "inner" and node.broadcast_side == "right":
        joins.append(node)
        node = _below_links(node.left)
    if len(joins) < 2 or not isinstance(node, _CHAIN_SOURCES):
        return []
    return joins


def _chain_rungs(capacity: int) -> List[int]:
    """The widths a join chain over a `capacity`-row source may run its
    later joins at, ascending: a sixty-fourth and an eighth of it (65,536
    and 524,288 rows at 4,194,304), each a capacity bucket, and each only
    where it is at least `auron.batch.capacity.min` rows.  None of them
    sorts anything, so unlike an aggregate's rungs they need no ceiling."""
    from auron_tpu.config import conf as _conf
    least = int(_conf.get("auron.batch.capacity.min"))
    return sorted({bucket_capacity(capacity // d) for d in (64, 8)
                   if capacity // d >= least})


def _pad_rows(tree, rows: int):
    """Every array of `tree` padded with zeros (dead rows) to `rows` rows."""
    return jax.tree.map(
        lambda x: jnp.pad(x, [(0, rows - x.shape[0])]
                          + [(0, 0)] * (x.ndim - 1)), tree)


# XLA:TPU lays a [rows, width] byte array out with its width padded to 128
# lanes, eight times a 16-byte string's bytes (4 GB at 33,554,432 rows): a
# join chain's sides gather a build side's string, and hand every string
# out of the choice, as 1-D columns of 32-bit words, laid out as they are


def _byte_words(data) -> List[Any]:
    """A [rows, width] byte array as 1-D columns of 32-bit words, four
    bytes a word, the first the lowest (of bytes, where the width is not a
    multiple of four)."""
    width = data.shape[1]
    if width % 4:
        return [data[:, k] for k in range(width)]
    d = data.astype(jnp.uint32)
    return [d[:, 4 * k] | d[:, 4 * k + 1] << 8 | d[:, 4 * k + 2] << 16
            | d[:, 4 * k + 3] << 24 for k in range(width // 4)]


def _word_bytes(words: List[Any]):
    """`_byte_words`' columns as the [rows, width] byte array again."""
    if words[0].dtype == jnp.uint8:
        return jnp.stack(words, axis=1)
    return jnp.stack([(w >> (8 * j) & 0xFF).astype(jnp.uint8)
                      for w in words for j in range(4)], axis=1)


def _as_words(cols) -> List[Any]:
    return [dataclasses.replace(c, data=_byte_words(c.data))
            if isinstance(c, DeviceStringColumn) else c for c in cols]


def _as_bytes(cols) -> List[Any]:
    return [dataclasses.replace(c, data=_word_bytes(c.data))
            if isinstance(c, DeviceStringColumn) else c for c in cols]


def _take_rows(c, bidx, ok):
    """Column `c`'s rows `bidx` where `ok`, and a dead row elsewhere — as
    `c.gather`, but a string's bytes gathered a word column at a time
    (`_byte_words`): a gather of [rows, width] bytes at the source's
    capacity inside a chain's full side would be laid out padded."""
    if not isinstance(c, DeviceStringColumn):
        return c.gather(bidx, ok)

    def take(x):
        return jnp.where(ok, jnp.take(x, bidx, axis=0, mode="fill",
                                      fill_value=0),
                         jnp.zeros((), x.dtype))
    return DeviceStringColumn(
        c.dtype, _word_bytes([take(w) for w in _byte_words(c.data)]),
        take(c.lengths), take(c.validity))


def _projection_schema(n: P.Projection, below: Schema) -> Schema:
    from auron_tpu.exprs.typing import infer_type
    return Schema(tuple(Field(nm, infer_type(x, below))
                        for nm, x in zip(n.names, n.exprs)))


def _linked_schema(node, known: Dict[int, Schema]) -> Schema:
    """The schema of `node`'s output, where `node` is a chain's link (or a
    join) above a node whose output schema `known` holds — without tracing
    the link."""
    if id(node) in known:
        return known[id(node)]
    below = _linked_schema(node.child, known)
    return below if isinstance(node, P.Filter) else \
        _projection_schema(node, below)


def _direct_key_types(ptypes: List[DataType], bkeys) -> bool:
    """`_direct_addressable` from the probe keys' types, for a probe side
    that is not traced yet."""
    return len(ptypes) == 1 and len(bkeys) == 1 and \
        isinstance(bkeys[0], DeviceColumn) and \
        (ptypes[0].is_integral or ptypes[0].id == TypeId.DATE32) and \
        ptypes[0].id == bkeys[0].dtype.id and \
        np.dtype(ptypes[0].numpy_dtype()) == bkeys[0].data.dtype


@dataclass
class _ChainJoin:
    """A later join of a join chain as its build half left it: what the
    probe half reads inside every side of the chain's choice."""
    label: str
    build: DeviceTable
    bkeys: List[Any]
    # (kmin, table, order, sorted_bh), or (order, sorted_bh) where the
    # keys are not directly addressable
    half: Tuple[Any, ...]
    trip: Any
    # the devices' choice of probe; None where the search is the only one
    dense: Any


def _direct_addressable(pkeys, bkeys) -> bool:
    """The static half of the direct-address probe's test: one key pair,
    both device columns of one integer or date type (not decimal, string
    or float — their device words are not the key's order)."""
    if len(pkeys) != 1 or len(bkeys) != 1:
        return False
    pk, bk = pkeys[0], bkeys[0]
    return all(isinstance(k, DeviceColumn) and
               (k.dtype.is_integral or k.dtype.id == TypeId.DATE32)
               for k in (pk, bk)) and \
        pk.dtype.id == bk.dtype.id and pk.data.dtype == bk.data.dtype


def _key_words(data: Array) -> Array:
    """Integer key data at a width that has an unsigned twin on the
    device (int8/int16 widen to int32)."""
    return data if data.dtype.itemsize >= 4 else data.astype(jnp.int32)


def _unsigned(x: Array) -> Array:
    """The same bits as an unsigned integer of the same width."""
    return lax.bitcast_convert_type(
        x, jnp.uint64 if x.dtype.itemsize == 8 else jnp.uint32)


def operator_labels(plan, conv_ctx) -> List[Tuple[int, Any, str]]:
    """(depth, node, "<kind>#<i>") for every operator of a stage plan,
    `<i>` its pre-order index, exchange and broadcast boundaries followed
    into their children.  The index depends on the plan's shape alone,
    so it is the same in every process and for every conversion of an
    equal plan; it names the operator's `jax.named_scope` inside the
    stage program (read back by `python -m auron_tpu.trace device`) and
    its line in `explain_stage`."""
    exchanges = getattr(conv_ctx, "exchanges", None) or {}
    broadcasts = getattr(conv_ctx, "broadcasts", None) or {}
    out: List[Tuple[int, Any, str]] = []
    seen = set()
    stack = [(0, plan)]
    while stack:
        depth, node = stack.pop()
        if not isinstance(node, P.PlanNode) or id(node) in seen:
            continue     # a union names one child once per partition
        seen.add(id(node))
        out.append((depth, node, f"{node.kind}#{len(out)}"))
        if isinstance(node, P.IpcReader):
            job = exchanges.get(node.resource_id) or \
                broadcasts.get(node.resource_id)
            kids = [job.child] if job is not None else []
        else:
            kids = P.plan_children(node)
        stack.extend((depth + 1, c) for c in reversed(kids))
    return out


def _peel_tail(plan, exchanges):
    """Split a converted plan into its driver-side tail — the root chain
    of single-partition ops (projection / sort / limit / renames) replayed
    through the SERIAL engine on the gathered table, the reference's final
    collect on the driver (TakeOrderedAndProject) — and the body the
    stage program runs.  Returns (tail, shadow_sort, body)."""
    tail: List[P.PlanNode] = []
    shadow_sort: Optional[P.Sort] = None
    while isinstance(plan, (P.Projection, P.Sort, P.Limit,
                            P.RenameColumns)):
        tail.append(plan)
        if isinstance(plan, P.Sort) and shadow_sort is None:
            shadow_sort = plan
        plan = plan.child
    # a root single-mode exchange feeding the tail is redundant: the host
    # gather itself is the "move everything to one place" step
    while isinstance(plan, P.IpcReader) and plan.resource_id in exchanges:
        job = exchanges[plan.resource_id]
        if job.partitioning.mode != "single":
            break
        plan = _require_native(job.child)
    return tail, shadow_sort, plan


def explain_stage(plan, conv_ctx,
                  stats: Optional[Dict[str, Any]] = None) -> str:
    """The stage path's EXPLAIN text: the driver-side tail, then every
    operator of the stage program under the label its device time is
    filed under.  `stats` (execute_plan_spmd's) marks each K=1 join with
    the probe it took, `direct` or `search`, each aggregate that chose a
    width with the input it worked on, `compact` or `full`, the width its
    body ran at (`rows`), the live rows of its input and the rung of the
    capacity ladder (`cap`), the first join of each join chain that chose
    a width with the side its later joins took (`chain=compact|full`), the
    width they ran at and the live rows it left, each operator whose
    output holds
    wide decimals with `dec128` and how many, and each boundary that
    crossed devices with what it moved."""
    stats = stats or {}
    probes = stats.get("join_probes") or {}
    aggs = stats.get("agg_inputs") or {}
    chains = stats.get("join_chains") or {}
    wide = stats.get("wide_columns") or {}
    crossed = {**(stats.get("exchanges") or {}),
               **(stats.get("broadcasts") or {})}
    exchanges = getattr(conv_ctx, "exchanges", None) or {}
    broadcasts = getattr(conv_ctx, "broadcasts", None) or {}
    tail, _shadow, body = _peel_tail(plan, exchanges)
    lines = [f"{node.kind} (driver tail, serial engine)" for node in tail]
    for depth, node, label in operator_labels(body, conv_ctx):
        detail = ""
        if isinstance(node, P.Agg):
            detail = f" mode={node.exec_mode}"
            if label in aggs:
                a = aggs[label]
                detail += (f" input={a['input']} rows={a['rows']}"
                           f" live={a['live']} of {a['capacity']}"
                           f" cap={a['cap']}")
        elif isinstance(node, (P.BroadcastJoin, P.HashJoin,
                               P.SortMergeJoin)):
            detail = f" type={node.join_type}"
            if label in probes:
                detail += f" probe={probes[label]}"
            if label in chains:
                c = chains[label]
                detail += (f" chain={c['chain']} rows={c['rows']}"
                           f" live={c['live']} of {c['capacity']}")
        elif isinstance(node, P.IpcReader):
            if node.resource_id in exchanges:
                detail = " exchange:" + \
                    exchanges[node.resource_id].partitioning.mode
            elif node.resource_id in broadcasts:
                detail = " broadcast"
            c = crossed.get(label)
            if c is not None and "quota" in c:
                detail += (
                    f" rows={c['rows']} moved={c['rows_moved']}"
                    f" recv_max={c['rows_recv_max']} quota={c['quota']}"
                    f" fill={c['fill_pct']:.2f}%"
                    f" bytes={c['moved_bytes']}/{c['buffer_bytes']}")
            elif c is not None:
                detail += (f" rows={c['rows']} slots={c['slots']}"
                           f" bytes={c['buffer_bytes']}")
        if label in wide:
            detail += f" dec128={wide[label]}"
        lines.append(f"{'  ' * depth}{label}{detail}")
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# plan walk (traced inside shard_map)
# ---------------------------------------------------------------------------

def _row_bytes(flat) -> int:
    """Bytes one row takes in a collective's buffers: a slot of every
    array, and its bit of the live mask (a byte)."""
    return 1 + sum(int(np.prod(a.shape[1:])) * a.dtype.itemsize
                   for a in flat)


class _StageTracer:
    def __init__(self, conv_ctx, bindings: Dict[str, DeviceTable],
                 axis, n_dev: int, labels: Dict[int, str],
                 shadow_sort: Optional[P.Sort] = None,
                 scan_rids: Optional[Dict[int, str]] = None,
                 axis_sizes: Optional[Tuple[int, ...]] = None,
                 match_factor: int = 1,
                 agg_cap_hint: int = 0,
                 join_compact: bool = True):
        self.exchanges = getattr(conv_ctx, "exchanges", None) or {}
        self.broadcasts = getattr(conv_ctx, "broadcasts", None) or {}
        # id(node) -> "<kind>#<i>" (operator_labels): the named scope
        # every operation the node's handler traces is filed under
        self.labels = labels
        self.bindings = bindings
        self.axis = axis
        self.n_dev = n_dev
        # multi-axis mesh (dcn, ici): sizes aligned with the axis tuple
        self.axis_sizes = axis_sizes
        # the driver-side global sort that re-orders (and re-limits) the
        # gathered result; per-partition top-k sorts it shadows are
        # dropped (the TakeOrderedAndProject pattern: partition top-k ->
        # single exchange -> global top-k)
        self.shadow_sort = shadow_sort
        self.scan_rids = scan_rids or {}
        # runtime guards: device booleans that invalidate the SPMD result
        # post-run; the driver fetches them with the output.  `guards`
        # are HARD (quota overflow, dup keys past the match factor, dup
        # keys under a semi-like join): fall back to serial.
        # `retry_guards` are join dup-key trips a pair-expansion retry
        # can fix.
        self.guards: List[Any] = []
        self.retry_guards: List[Any] = []
        # `shrink_guards` trip when an agg's group count overflows the
        # shrunk static capacity (auron.spmd.agg.capacity.hint); the
        # driver climbs a capacity ladder (4x wider per retry, x16 at
        # most, then shrinking off: execute_plan_spmd).
        self.shrink_guards: List[Any] = []
        # `join_guards` trip when a K-expanded join's live output
        # overflows the compaction target; the driver retries with join
        # compaction disabled — an INDEPENDENT retry dimension so a
        # genuinely fanning-out join doesn't also lose the agg shrink
        self.join_guards: List[Any] = []
        # one entry per K=1 join, in trace order: (operator label, the
        # number of devices that took the direct-address probe — a device
        # scalar — or None where the join traced the search alone)
        self.probes: List[Tuple[str, Any]] = []
        # one entry per aggregate traced with a choice of width, in trace
        # order: (its label, its input's slots over all devices, the rung
        # of the capacity ladder and the widths it chose among; [devices
        # that compacted the input, live input rows, then the devices at
        # each of those widths] — a replicated int64 device vector)
        self.agg_inputs: List[Tuple[Dict[str, Any], Any]] = []
        # one entry per join chain traced with a choice of width, likewise
        # (its first join's label, its slots over all devices and the
        # widths; [devices that compacted, live rows after the first join,
        # then the devices at each width])
        self.join_chains: List[Tuple[Dict[str, Any], Any]] = []
        # while a side of a chain's choice is traced: (the chain's first
        # join, what stands for its output on that side, the later joins
        # by node id as their build halves left them)
        self._chain: Optional[Tuple[Any, Callable[[], DeviceTable],
                                    Dict[int, _ChainJoin]]] = None
        # one entry per exchange or broadcast boundary that crossed
        # devices, in trace order: (what is known of it at trace time,
        # its counts — a replicated int64 device vector); a one-device
        # program has none (_count_exchange, _count_broadcast)
        self.crossings: List[Tuple[Dict[str, Any], Any]] = []
        # operator label -> wide decimal columns (precision over 18, two
        # words a value) in the table it handed on
        self.wide_columns: Dict[str, int] = {}
        # join pair-expansion factor (1 = single-candidate probe)
        self.match_factor = max(1, int(match_factor))
        # post-agg static capacity (rows/device); 0 keeps input capacity
        self.agg_cap_hint = max(0, int(agg_cap_hint))
        # compact K-expanded join outputs back to pre-expansion capacity
        self.join_compact = bool(join_compact)

    def _axis_index(self):
        """Global device id; for a (dcn, ici) mesh the layout is
        dcn_rank * n_ici + ici_rank (hierarchical_repartition contract)."""
        if isinstance(self.axis, tuple):
            a_dcn, a_ici = self.axis
            n_ici = self.axis_sizes[1]
            return (lax.axis_index(a_dcn) * n_ici +
                    lax.axis_index(a_ici)).astype(jnp.int32)
        return lax.axis_index(self.axis)

    # -- expression eval -------------------------------------------------

    def _eval_exprs(self, exprs, t: DeviceTable) -> List[Any]:
        for x in exprs:
            if not device_capable(x, t.schema, frozenset(), wide=True):
                raise SpmdUnsupported(f"expr not device-capable: {x.kind}")
            if _tree_has(x, ("row_num", "monotonically_increasing_id",
                             "py_udf_wrapper", "scalar_subquery")):
                raise SpmdUnsupported(f"stateful expr in SPMD: {x.kind}")
        ctx = EvalCtx(cols=list(t.cols), schema=t.schema,
                      num_rows=jnp.sum(t.live.astype(jnp.int32)),
                      capacity=t.capacity,
                      partition_id=self._axis_index(),
                      row_base=jnp.int64(0))
        return [evaluate(x, ctx) for x in exprs]

    def _eval_keys(self, exprs, t: DeviceTable, what: str) -> List[Any]:
        """Expressions whose order or hash the program needs.  A wide
        decimal is a value here, never a key: `iter_spmd_rejections`
        refuses such a plan by name before anything is read; this is the
        same refusal for a plan whose schemas it could not follow."""
        cols = self._eval_exprs(exprs, t)
        for c in cols:
            if isinstance(c, DeviceDecimal128Column):
                raise SpmdUnsupported(
                    f"a wide decimal ({c.dtype!r}) as {what}")
        return cols

    # -- node dispatch -----------------------------------------------------

    def eval_node(self, node) -> DeviceTable:
        if not isinstance(node, P.PlanNode):
            raise SpmdUnsupported(f"non-native section: {type(node).__name__}")
        handler = getattr(self, f"_do_{node.kind}", None)
        if handler is None:
            raise SpmdUnsupported(f"operator not SPMD-compilable: {node.kind}")
        # HLO metadata only: neither _PROGRAM_CACHE's key nor JAX's
        # persistent-cache key sees it
        label = self.labels.get(id(node), node.kind)
        with jax.named_scope(label):
            out = handler(node)
        n_wide = sum(isinstance(c, DeviceDecimal128Column) for c in out.cols)
        if n_wide:
            self.wide_columns[label] = n_wide
        return out

    # sources ---------------------------------------------------------------

    def _binding(self, rid: str, schema: Schema) -> DeviceTable:
        if rid not in self.bindings:
            raise SpmdUnsupported(f"unbound resource {rid!r}")
        return self.bindings[rid]

    def _do_ffi_reader(self, n: P.FFIReader) -> DeviceTable:
        return self._binding(n.resource_id, n.schema)

    def _do_parquet_scan(self, n: P.ParquetScan) -> DeviceTable:
        # scans were pre-materialized by the driver (host IO) and sharded
        # over the mesh under deterministic walk-order rids
        return self._binding(self.scan_rids.get(id(n), "?"), n.schema)

    def _do_orc_scan(self, n: P.OrcScan) -> DeviceTable:
        return self._binding(self.scan_rids.get(id(n), "?"), n.schema)

    def _do_ipc_reader(self, n: P.IpcReader) -> DeviceTable:
        # an IpcReader is how the converted plan references an exchange or
        # broadcast boundary; inline it as a collective
        rid = n.resource_id
        label = self.labels.get(id(n), n.kind)
        if rid in self.exchanges:
            job = self.exchanges[rid]
            child = self.eval_node(_require_native(job.child))
            with jax.named_scope("exchange"):
                return self._exchange(child, job.partitioning, label)
        if rid in self.broadcasts:
            job = self.broadcasts[rid]
            child = self.eval_node(_require_native(job.child))
            with jax.named_scope("broadcast"):
                return self._broadcast(child, label)
        return self._binding(rid, n.schema)

    # exchanges --------------------------------------------------------------

    def _exchange(self, t: DeviceTable, part: P.Partitioning,
                  label: str) -> DeviceTable:
        n_dev = self.n_dev
        if n_dev == 1:
            # single-device axis: every row already lives on its
            # destination — the exchange is an identity, and the quota
            # machinery would only DOUBLE the buffer (capacity x margin)
            # for nothing (a real cost at sf10 single-chip shapes)
            return t
        if part.mode == "hash":
            keys = self._eval_keys(part.expressions, t, "exchange key")
            h = H.hash_columns(keys, seed=42)
            pid = H.pmod(h, n_dev).astype(jnp.int32)
        elif part.mode == "round_robin":
            base = self._axis_index().astype(jnp.int32)
            pid = (base + jnp.arange(t.capacity, dtype=jnp.int32)) % n_dev
        elif part.mode == "single":
            pid = jnp.zeros(t.capacity, jnp.int32)
        elif part.mode == "range":
            # sampled-bounds range ids (shared kernel with the serial
            # repartitioner), then bucket -> device by modulo: SPMD
            # bodies are order-insensitive, so range locality only
            # matters to the driver-side tail sort, not device placement
            from auron_tpu.ops.shuffle.partitioner import (
                encoded_range_bounds, range_ids_from_words,
            )
            from auron_tpu.ops.sort_keys import encode_sort_keys as _enc
            keys = self._eval_keys(
                tuple(s.child for s in part.sort_orders), t, "exchange key")
            orders = tuple((s.asc, s.nulls_first)
                           for s in part.sort_orders)
            words = _enc(keys, orders)
            bounds = encoded_range_bounds(part.range_bounds,
                                          part.sort_orders, orders)
            pid = range_ids_from_words(words, bounds, t.capacity) % n_dev
        else:
            raise SpmdUnsupported(f"partitioning mode {part.mode!r}")
        flat, treedef = jax.tree.flatten(t.cols)
        row_bytes = _row_bytes(flat)
        # bounded quota for spreading modes (hash/rr): received buffers
        # stay O(global/n_dev * margin); a single-partition exchange
        # legitimately funnels everything to one device, so it keeps the
        # full-capacity quota.  Overflow (quota exceeded under skew) trips
        # a runtime guard -> driver falls back to the serial engine.
        # only single (and a degenerate 1-partition range — all ids 0)
        # actually funnel everything to one device; hash/round-robin
        # spread over n_dev regardless of the plan's num_partitions,
        # while range spreads over at most its num_partitions buckets
        funnel = part.mode == "single" or (
            part.mode == "range" and part.num_partitions <= 1)
        spread = part.num_partitions if part.mode == "range" else n_dev
        if isinstance(self.axis, tuple):
            # 2-D (dcn, ici) mesh: two-stage exchange so every row crosses
            # the slow DCN axis at most once (SURVEY 2.5 comm-backend
            # row).  Stage 1 spreads over only the n_ici LOCAL
            # destinations, so its quota is sized for n_ici — an
            # n_dev-sized quota would overflow on uniform data whenever
            # n_dcn > margin
            a_dcn, a_ici = self.axis
            n_dcn, n_ici = self.axis_sizes
            q1 = t.capacity if funnel \
                else bounded_quota(t.capacity, min(n_ici, spread))
            outs, live, ovf = hierarchical_repartition(
                flat, pid, t.live, a_ici, a_dcn, n_ici, n_dcn,
                quota=q1, bound_stage2=not funnel)
            any_ovf = lax.psum(
                lax.psum(ovf.astype(jnp.int32), a_ici), a_dcn) > 0
            # the first stage's blocks go to the n_ici local chips, each
            # row with its int32 route; the second sends what it received
            quota, blocks = q1, n_ici
            buffer_bytes = (row_bytes + 4) * n_ici * q1 \
                + row_bytes * live.shape[0]
        else:
            quota = t.capacity if funnel \
                else bounded_quota(t.capacity, min(n_dev, spread))
            outs, live, ovf = all_to_all_repartition(flat, pid, t.live,
                                                     self.axis, n_dev,
                                                     quota=quota)
            any_ovf = lax.psum(ovf.astype(jnp.int32), self.axis) > 0
            blocks, buffer_bytes = n_dev, row_bytes * n_dev * quota
        self.guards.append(any_ovf)
        with jax.named_scope("count"):
            self._count_exchange(
                {"label": label, "kind": "exchange", "mode": part.mode,
                 "quota": quota, "row_bytes": row_bytes,
                 "buffer_bytes": buffer_bytes}, pid, t.live, live, blocks)
        cols = jax.tree.unflatten(treedef, outs)
        return DeviceTable(t.schema, cols, live)

    def _count_exchange(self, what: Dict[str, Any], pid, live_in, live_out,
                        blocks: int) -> None:
        """What an exchange moved, counted from its routing and from what
        arrived, not taken from the kernel: live rows in, those bound for
        another device, the most any device received, and the fullest
        (source, destination) block of the first all_to_all before its
        quota cut it (`blocks` destinations a device; past the quota the
        overflow guard has tripped).  Replicated: one psum, one pmax."""
        sent = destination_counts(pid, live_in, self.n_dev)
        rows_in = jnp.sum(sent, dtype=jnp.int32)
        stayed = jnp.take(sent, self._axis_index())
        fullest = jnp.max(jnp.sum(sent.reshape(-1, blocks), axis=0,
                                  dtype=jnp.int32))
        sums = lax.psum(jnp.stack([rows_in, rows_in - stayed])
                        .astype(jnp.int64), self.axis)
        # a device's counts fit 32 bits, and the TPU lowers no 64-bit
        # all-reduce but the sum
        tops = lax.pmax(jnp.stack([
            jnp.sum(live_out, dtype=jnp.int32), fullest]), self.axis)
        self.crossings.append(
            (what, jnp.concatenate([sums, tops.astype(jnp.int64)])))

    def _broadcast(self, t: DeviceTable, label: str) -> DeviceTable:
        flat, treedef = jax.tree.flatten(t.cols)
        if isinstance(self.axis, tuple):
            live = t.live
            for ax in reversed(self.axis):    # gather ICI first, then DCN
                flat, live = broadcast_all_gather(flat, live, ax)
        else:
            flat, live = broadcast_all_gather(flat, t.live, self.axis)
        if self.n_dev > 1:
            # every device holds the same gathered rows: its own count
            # is the replicated one
            with jax.named_scope("count"):
                self.crossings.append((
                    {"label": label, "kind": "broadcast",
                     "slots": live.shape[0],
                     "buffer_bytes": _row_bytes(flat) * live.shape[0]},
                    jnp.sum(live, dtype=jnp.int64)[None]))
        cols = jax.tree.unflatten(treedef, flat)
        return DeviceTable(t.schema, cols, live)

    # row ops -----------------------------------------------------------------

    def _concat_tables(self, schema: Schema,
                       tables: List[DeviceTable]) -> DeviceTable:
        from auron_tpu.columnar.batch import concat_device_columns
        cols = [concat_device_columns([t.cols[i] for t in tables])
                for i in range(len(schema))]
        live = jnp.concatenate([t.live for t in tables])
        return DeviceTable(schema, cols, live)

    def _do_union(self, n: P.Union) -> DeviceTable:
        # SPMD union: every device holds a shard of every child, so the
        # per-partition enumeration (proto:542-552 — one UnionInput per
        # child partition) collapses to ONE concat of the child; a child
        # whose partitions are each referenced m times contributes m
        # replicated copies (rows-twice semantics of duplicate inputs)
        by_child: Dict[int, Any] = {}
        order: List[int] = []
        for i in n.inputs:
            if id(i.child) not in by_child:
                by_child[id(i.child)] = (i.child, {})
                order.append(id(i.child))
            by_child[id(i.child)][1].setdefault(i.partition, 0)
            by_child[id(i.child)][1][i.partition] += 1
        tables: List[DeviceTable] = []
        for cid in order:
            child, part_counts = by_child[cid]
            counts = set(part_counts.values())
            if len(counts) != 1:
                raise SpmdUnsupported(
                    "union references a child's partitions unevenly")
            t = self.eval_node(child)
            for _ in range(counts.pop()):
                tables.append(t)
        return self._concat_tables(n.schema, tables)

    def _do_expand(self, n: P.Expand) -> DeviceTable:
        # grouping-sets: each projection contributes one replicated copy
        # of the child rows (expand_exec.rs:40)
        t = self.eval_node(n.child)
        schema = Schema(tuple(Field(nm, dt)
                              for nm, dt in zip(n.names, n.types)))
        parts = [DeviceTable(schema, self._eval_exprs(proj, t), t.live)
                 for proj in n.projections]
        return self._concat_tables(schema, parts)

    def _do_filter(self, n: P.Filter) -> DeviceTable:
        t = self.eval_node(n.child)
        live = t.live
        for p in n.predicates:
            [m] = self._eval_exprs((p,), t)
            live = jnp.logical_and(
                live, jnp.logical_and(m.validity, m.data.astype(bool)))
        return DeviceTable(t.schema, t.cols, live)

    def _do_projection(self, n: P.Projection) -> DeviceTable:
        t = self.eval_node(n.child)
        cols = self._eval_exprs(n.exprs, t)
        return DeviceTable(_projection_schema(n, t.schema), cols, t.live)

    def _do_rename_columns(self, n: P.RenameColumns) -> DeviceTable:
        t = self.eval_node(n.child)
        return DeviceTable(t.schema.rename(tuple(n.names)), t.cols, t.live)

    def _do_coalesce_batches(self, n: P.CoalesceBatches) -> DeviceTable:
        return self.eval_node(n.child)

    def _do_debug(self, n: P.Debug) -> DeviceTable:
        return self.eval_node(n.child)

    # aggregation --------------------------------------------------------------

    def _agg_exec_meta(self, n: P.Agg, child_schema: Schema):
        """Instantiate AggExec purely for its spec/schema metadata."""
        from auron_tpu.ops.agg.exec import AggExec
        from auron_tpu.ops.agg.functions import HostAggSpec

        class _SchemaOp:
            def __init__(self, schema):
                self.schema = schema
                self.metrics = None
        dummy = _SchemaOp(child_schema)
        dummy.children = []
        from auron_tpu.runtime.metrics import MetricNode
        dummy.metrics = MetricNode("src")
        agg = AggExec(dummy, n.exec_mode, n.grouping, n.grouping_names,
                      n.aggs, n.agg_names, False, wide=True)
        if any(isinstance(s, HostAggSpec) for s in agg.specs):
            raise SpmdUnsupported("host-path agg function in SPMD")
        return agg

    def _admitting_exchange_mode(self, agg) -> Optional[str]:
        part = _feeding_exchange(agg, self.exchanges)
        return part.mode if part is not None else None

    def _do_agg(self, n: P.Agg) -> DeviceTable:
        """One aggregate.  Its output is cut to `new_cap` rows (the
        capacity hint's bucket) wherever its input is larger than that,
        and its cost follows the width of the table it is GIVEN, so the
        program counts the input's live rows and chooses that width, per
        device (one `lax.switch`, no collective inside a branch), from a
        short ladder — the narrowest that holds the live rows:

        - a rung below `new_cap` (`_agg_rungs`), or `new_cap` itself where
          the input is larger — compact: the live rows are brought to the
          front of a table that wide (`_compact_front`), the body runs at
          that width, and the groups are handed on with dead rows behind
          them at the width the operator hands on anyway (`new_cap`, or
          the input's capacity where nothing is cut), so nothing
          downstream changes shape.  An aggregate has no more groups than
          live rows (a global one over no rows has its one identity row),
          so these sides' output fits and their guard flag is constant
          False;
        - otherwise — full: the body at the input's capacity, then, where
          that is larger than `new_cap`, `_shrink_front`'s cut to it and
          its flag (`n_groups > new_cap`).

        Every side hands back a table of one shape and schema; the `psum`
        that makes the flag the shrink guard sits after the choice.  With
        the shrink off, or an input no wider than the narrowest rung,
        there is neither cut nor choice: the body at the input's capacity
        and nothing else."""
        from auron_tpu.ops.agg.exec import _group_reduce_body
        if n.exec_mode == "single" and self.n_dev > 1 and \
                not _single_agg_ok(n, self.exchanges):
            # a single-mode agg is per-partition; on a sharded SOURCE its
            # device-local groups would diverge from the collapsed serial
            # oracle — but directly after an exchange the device IS the
            # partition, so per-device reduction is exactly the
            # per-partition semantics (empty devices emit zero groups)
            raise SpmdUnsupported(
                "single-mode agg needs an exchange (or partial/final "
                "shape) on a multi-device mesh")
        t = self.eval_node(n.child)
        agg = self._agg_exec_meta(n, t.schema)
        final = n.exec_mode in ("final", "single")
        out_schema = agg.schema if final else agg._state_schema()

        def aggregate_over(t: DeviceTable) -> Tuple[DeviceTable, Array]:
            """The aggregate over `t`, at `t`'s capacity: its groups at the
            front of the table, and how many there are."""
            merge = n.exec_mode == "final"
            keys = self._eval_keys(n.grouping, t, "group key")
            nk = len(n.grouping)
            if merge:
                vcols: List[List[Any]] = []
                off = nk
                for spec in agg.specs:
                    k = len(spec.state_fields())
                    vcols.append(t.cols[off:off + k])
                    off += k
            else:
                vcols = []
                for a in n.aggs:
                    vcols.append(self._eval_exprs(a.children, t)
                                 if a.children else [])
            out_cols, n_groups = _group_reduce_body(
                keys, vcols, t.live, agg.specs, agg._key_orders(), merge)
            if nk == 0 and final:
                # a global agg over an empty input still emits the identity
                # row (count=0, sum=null — the serial _empty_global_agg
                # contract).  The clipped row-0 states are exactly the
                # identities: count's eval_final forces validity over the
                # zeroed data, every other agg finalizes to null.  Under a
                # round-robin exchange every device IS a live partition, so
                # each empty device owes its own identity row; otherwise
                # (single exchange / partial-final) only device 0 does.
                empty = n_groups == 0
                if n.exec_mode == "single" and \
                        self._admitting_exchange_mode(n) == "round_robin":
                    force = empty
                else:
                    force = jnp.logical_and(self._axis_index() == 0, empty)
                n_groups = jnp.where(force, 1, n_groups)
            live = jnp.arange(t.capacity, dtype=jnp.int32) < n_groups
            if final:
                final_cols = list(out_cols[:nk])
                off = nk
                for spec in agg.specs:
                    k = len(spec.state_fields())
                    final_cols.append(
                        spec.eval_final(out_cols[off:off + k]))
                    off += k
                out_cols = final_cols
            return DeviceTable(out_schema, out_cols, live), n_groups

        new_cap = bucket_capacity(self.agg_cap_hint) \
            if self.agg_cap_hint > 0 else 0
        cut = 0 < new_cap < t.capacity
        out_cap = new_cap if cut else t.capacity
        # the widths a compacted input may take, ascending
        widths = _agg_rungs(new_cap, t.capacity) + ([new_cap] if cut else [])
        if not widths:
            # the shrink is off, or the input is no wider than the
            # narrowest rung: no cut, no guard, no choice
            return aggregate_over(t)[0]
        n_live = jnp.sum(t.live.astype(jnp.int32))

        def compact_side(width: int):
            def side():
                out, _n_groups = aggregate_over(
                    _compact_front(t, n_live, width))
                with jax.named_scope("compact"):
                    cols, live = _pad_rows((out.cols, out.live), out_cap)
                return cols, live, jnp.bool_(False)
            return side

        def full_side():
            out, n_groups = aggregate_over(t)
            over = jnp.bool_(False)
            if cut:
                out, over = self._shrink_front(out, n_groups, new_cap)
            return out.cols, out.live, over

        # the narrowest width that holds the live rows; past them all, full
        chosen = sum((n_live > w).astype(jnp.int32) for w in widths)
        with inside_branch():
            cols, live, over = lax.switch(
                chosen, [compact_side(w) for w in widths] + [full_side])
        if cut:
            self.shrink_guards.append(
                lax.psum(over.astype(jnp.int32), self.axis) > 0)
        self.agg_inputs.append((
            {"label": self.labels.get(id(n), n.kind),
             "capacity": t.capacity * self.n_dev, "cap": new_cap,
             "widths": (*widths, t.capacity)},
            self._choice_counts(chosen, n_live, len(widths))))
        return DeviceTable(out_schema, cols, live)

    def _choice_counts(self, chosen, n_live, n_widths: int):
        """For the driver's counter of a choice of width among `n_widths`
        and the input's own: the devices that compacted, the live rows
        they looked at and the devices at each width, summed over
        devices."""
        took = jnp.arange(n_widths + 1, dtype=jnp.int32) == chosen
        return lax.psum(jnp.concatenate([
            jnp.stack([(chosen < n_widths).astype(jnp.int32), n_live]),
            took.astype(jnp.int32)]).astype(jnp.int64), self.axis)

    def _shrink_front(self, t: DeviceTable, n_live,
                      new_cap: int) -> Tuple[DeviceTable, Array]:
        """Cut a front-compacted table (all live rows at indices
        [0, n_live)) down to `new_cap` rows, and say whether that lost a
        row.  Aggs are the plan's cardinality reducers, but the
        mask-liveness model keeps their INPUT capacity — so without this
        every downstream exchange / join / sort pays input-scale cost for
        a handful of groups (round-4 root cause of the stage path losing
        to serial at bench scale).  `over` (more groups than `new_cap`)
        becomes the aggregate's shrink guard; the driver climbs a
        capacity ladder (4x per retry, then shrink off)."""
        with jax.named_scope("compact"):
            cols = [jax.tree.map(lambda x: x[:new_cap], c) for c in t.cols]
            return (DeviceTable(t.schema, cols, t.live[:new_cap]),
                    n_live > new_cap)

    # joins ---------------------------------------------------------------------

    def _do_broadcast_join(self, n: P.BroadcastJoin) -> DeviceTable:
        # build side is REPLICATED on every device: emitting unmatched
        # build rows (full/right) would duplicate them per device, so
        # those types are precheck-rejected for broadcast joins
        if self._chain is not None:
            head, head_out, later = self._chain
            if n is head:
                return head_out()
            if id(n) in later:
                return self._chain_probe(n, later[id(n)])
        elif self.match_factor == 1:
            joins = join_chain(n)
            if joins and _chain_rungs(self._source_capacity(joins[-1])):
                return self._join_chain(joins)
        return self._join(n.left, n.right, n.on, n.join_type,
                          build_side=n.broadcast_side,
                          existence_name=n.existence_output_name,
                          label=self.labels.get(id(n), n.kind))

    def _do_hash_join(self, n: P.HashJoin) -> DeviceTable:
        # colocation vetted by precheck_plan: a shuffled hash join is
        # only correct per-device when both sides were hash-exchanged on
        # the join keys
        return self._join(n.left, n.right, n.on, n.join_type,
                          build_side=n.build_side,
                          existence_name=n.existence_output_name,
                          colocated=True,
                          label=self.labels.get(id(n), n.kind))

    def _do_broadcast_join_build_hash_map(self, n) -> DeviceTable:
        return self.eval_node(n.child)

    def _do_sort_merge_join(self, n: P.SortMergeJoin) -> DeviceTable:
        # SMJ in SPMD: both sides arrive hash-exchanged on their join
        # keys, so equal keys are COLOCATED and the per-device probe
        # applies (the mid-plan sorts under an SMJ are no-ops here —
        # neither probe needs its input ordered).
        # Duplicate build keys retry with K-way pair expansion; key runs
        # wider than the factor fall back to the streaming serial SMJ.
        # colocation was vetted by precheck_plan (the one authoritative
        # copy — it runs before any source materialization)
        return self._join(n.left, n.right, n.on, n.join_type,
                          build_side="right",
                          existence_name=n.existence_output_name,
                          colocated=True,
                          label=self.labels.get(id(n), n.kind))

    _JOIN_TYPES = ("inner", "left", "left_semi", "left_anti", "existence")
    _JOIN_TYPES_COLOCATED = _JOIN_TYPES + ("full", "right")

    def _join(self, left_ir, right_ir, on, join_type: str,
              build_side: str, existence_name: str = "exists",
              colocated: bool = False, label: str = "") -> DeviceTable:
        allowed = self._JOIN_TYPES_COLOCATED if colocated \
            else self._JOIN_TYPES
        if join_type not in allowed:
            raise SpmdUnsupported(f"SPMD join type {join_type!r}")
        if build_side != "right":
            raise SpmdUnsupported("SPMD join requires build_side=right")
        probe = self.eval_node(left_ir)
        build = self.eval_node(right_ir)
        with jax.named_scope("probe"):
            pkeys = self._eval_keys(on.left_keys, probe, "join key")
        with jax.named_scope("build"):
            bkeys = self._eval_keys(on.right_keys, build, "join key")
        semi_like = join_type in ("left_semi", "left_anti", "existence")
        K = 1 if semi_like else self.match_factor
        if K <= 1 and _direct_addressable(pkeys, bkeys):
            # one integer key: the program looks at the build keys and
            # takes the direct-address probe where their range fits
            bidx, ok = self._lookup_adaptive(probe, build, pkeys[0],
                                             bkeys[0], semi_like, label)
            with jax.named_scope("probe"):
                return self._join_emit(probe, build, bidx, ok, join_type,
                                       existence_name)
        # everything else traces the sorted-hash search, operation for
        # operation as before there was a choice
        with jax.named_scope("build"):
            order, sorted_bh = self._sorted_build_hashes(build, bkeys)
        with jax.named_scope("probe"):
            ph = self._probe_hashes(probe, pkeys)
            if K > 1:
                return self._join_expanded(probe, build, pkeys, bkeys,
                                           order, sorted_bh, ph, join_type,
                                           existence_name, K)
            self.probes.append((label, None))
            self._trip_guard(
                self._search_trip(bkeys, order, sorted_bh, semi_like),
                semi_like)
            bidx, ok = self._search_probe(build, pkeys, bkeys, order,
                                          sorted_bh, ph)
            return self._join_emit(probe, build, bidx, ok, join_type,
                                   existence_name)

    @staticmethod
    def _sorted_build_hashes(build, bkeys):
        """(order, sorted_bh): the build side as a sorted u64 hash array,
        dead and null-key rows under a sentinel at its end."""
        from auron_tpu.ops.joins.kernel import _NULL_BUILD, join_key_hash
        bh, bvalid = join_key_hash(bkeys, build.capacity)
        bh = jnp.where(jnp.logical_and(build.live, bvalid), bh,
                       _NULL_BUILD)
        order = stable_argsort(bh)
        return order, jnp.take(bh, order)

    @staticmethod
    def _probe_hashes(probe, pkeys):
        from auron_tpu.ops.joins.kernel import _NULL_PROBE, join_key_hash
        ph, pvalid = join_key_hash(pkeys, probe.capacity)
        return jnp.where(jnp.logical_and(probe.live, pvalid), ph,
                         _NULL_PROBE)

    @staticmethod
    def _cols_eq(a_cols, b_cols, ok):
        """AND of null-safe per-column equality over aligned column
        lists — THE key-equality rule (collision filter); every caller
        must go through here so string/decimal semantics can never
        diverge between the probe check and the build-run check."""
        for a, b in zip(a_cols, b_cols):
            if isinstance(a, DeviceStringColumn):
                from auron_tpu.exprs import strings_device as S
                eq = S.string_eq(a, b)
            else:
                eq = a.data == b.data
            ok = jnp.logical_and(ok, jnp.logical_and(
                eq, jnp.logical_and(a.validity, b.validity)))
        return ok

    def _exact_eq(self, pkeys, bkeys, bidx, hit):
        """Exact key equality for candidate pairs (hash-collision
        filter); pkeys are already pair-aligned."""
        return self._cols_eq(
            pkeys, [bk.gather(bidx, hit) for bk in bkeys], hit)

    def _join_outer_tail(self, schema, probe, build, out_cols, ok, bidx,
                         live1):
        """full/right tail: colocated builds, so unmatched build rows
        emit locally — probe segment + null-padded unmatched-build
        segment concatenated."""
        from auron_tpu.ops.joins.kernel import null_columns_like
        t1 = DeviceTable(schema, out_cols, live1)
        matched = jnp.zeros(build.capacity, bool).at[
            jnp.where(ok, bidx, build.capacity)].set(True, mode="drop")
        live2 = jnp.logical_and(build.live, jnp.logical_not(matched))
        null_probe = [
            DeviceDecimal128Column.nulls(f.dtype, build.capacity)
            if f.dtype.is_wide_decimal
            else null_columns_like([f], build.capacity)[0]
            for f in probe.schema.fields]
        t2 = DeviceTable(schema, null_probe + list(build.cols), live2)
        return self._concat_tables(schema, [t1, t2])

    # -- the K=1 lookup: one contract, (bidx, ok), two ways to compute it --
    #
    # Single-candidate probe (match_factor=1): every probe row gets at
    # most one build row.  Duplicate build keys would need pair expansion,
    # so a runtime guard detects them.  For pair-emitting join types the
    # trip is RETRYABLE (the driver re-traces with the expansion factor).
    # Semi/anti/existence are probe-preserving, so TRUE duplicate keys are
    # harmless — any candidate of an equal-key run carries the same key.
    # This is what lets the TPC-DS semi/anti families (customer EXISTS
    # over fact tables: massively duplicate build keys) ride the mesh at
    # K=1.

    def _trip_guard(self, trip, semi_like: bool) -> None:
        """File one join's local trip: hard under a semi-like join (a
        hash collision), retryable under a pair-emitting one (a
        duplicate build key)."""
        tripped = lax.psum(trip.astype(jnp.int32), self.axis) > 0
        (self.guards if semi_like else self.retry_guards).append(tripped)

    def _search_trip(self, bkeys, order, sorted_bh, semi_like: bool):
        """The sorted-hash side's trip, from adjacent equal non-sentinel
        hashes after the sort: any such pair under a pair-emitting join;
        under a semi-like join only a hash COLLISION (equal hashes whose
        exact keys differ), since the leftmost candidate of an equal-hash
        run must carry the probed key."""
        from auron_tpu.ops.joins.kernel import _NULL_BUILD
        adj = jnp.logical_and(sorted_bh[1:] == sorted_bh[:-1],
                              sorted_bh[1:] != _NULL_BUILD)
        if not semi_like:
            return jnp.any(adj)
        keys_eq = self._cols_eq(
            [bk.gather(order[:-1], adj) for bk in bkeys],
            [bk.gather(order[1:], adj) for bk in bkeys],
            jnp.ones(adj.shape, bool))
        return jnp.any(jnp.logical_and(adj, jnp.logical_not(keys_eq)))

    def _search_probe(self, build, pkeys, bkeys, order, sorted_bh, ph):
        """Binary search of the probe hashes in the sorted build hashes:
        log2(slots) + 1 dependent gathers of the probe's capacity, then
        the exact-key filter for hash collisions."""
        pos = jnp.clip(jnp.searchsorted(sorted_bh, ph), 0,
                       build.capacity - 1)
        hit = jnp.take(sorted_bh, pos) == ph
        bidx = jnp.take(order, pos)
        return bidx, self._exact_eq(pkeys, bkeys, bidx, hit)

    def _lookup_adaptive(self, probe, build, pk, bk, semi_like: bool,
                         label: str):
        """(bidx, ok) for one integer key pair, the way chosen INSIDE the
        program from the build keys it is looking at: where the live,
        non-null keys span less than the build side's own capacity
        (surrogate keys of a dimension table do), the row's position in a
        table indexed by `key - min` IS the lookup — one gather of the
        probe's capacity, exact, no hash, no sort, no collision filter
        (Spark's LongHashedRelation "dense mode").  Otherwise the
        sorted-hash search.  `lax.cond`: only the chosen side executes,
        each device decides for its own build shard, and no collective
        sits inside a branch."""
        pdata, bdata = _key_words(pk.data), _key_words(bk.data)
        bvalid, kmin, dense = self._key_range(build, bk, bdata)

        def direct():
            table, trip = self._direct_table(build, bvalid, bdata, kmin,
                                             semi_like)
            return (*self._direct_probe(probe, pk, pdata, kmin, table), trip)

        def search():
            with jax.named_scope("build"):
                order, sorted_bh = self._sorted_build_hashes(build, [bk])
            with jax.named_scope("probe"):
                ph = self._probe_hashes(probe, [pk])
                trip = self._search_trip([bk], order, sorted_bh, semi_like)
                bidx, ok = self._search_probe(build, [pk], [bk], order,
                                              sorted_bh, ph)
                return bidx, ok, trip

        bidx, ok, trip = lax.cond(dense, direct, search)
        self._trip_guard(trip, semi_like)
        # how many devices took the direct side, for the driver's counter
        self.probes.append(
            (label, lax.psum(dense.astype(jnp.int32), self.axis)))
        return bidx, ok

    @staticmethod
    def _key_range(build, bk, bdata):
        """(bvalid, kmin, dense): the build rows with a key, the least of
        their keys, and whether the keys span less than the build side's
        capacity — the direct-address table's test."""
        with jax.named_scope("build"):
            bvalid = jnp.logical_and(build.live, bk.validity)
            info = jnp.iinfo(bdata.dtype)
            kmin = jnp.min(jnp.where(bvalid, bdata, info.max))
            kmax = jnp.max(jnp.where(bvalid, bdata, info.min))
            # an unsigned wrap-around difference is exact for any pair of
            # signed keys: no int64 extreme overflows it
            dense = jnp.logical_and(
                jnp.any(bvalid),
                _unsigned(kmax) - _unsigned(kmin) < build.capacity)
        return bvalid, kmin, dense

    @staticmethod
    def _direct_table(build, bvalid, bdata, kmin, semi_like: bool):
        """(table, trip): each key's build row at `key - kmin`, -1 where
        no key lands, and the duplicate-key trip."""
        cap = build.capacity
        with jax.named_scope("build"):
            # dead and null-key rows scatter out of range and drop
            slot = jnp.where(bvalid, _unsigned(bdata) - _unsigned(kmin),
                             cap).astype(jnp.int32)
            table = jnp.full(cap, -1, jnp.int32).at[slot].set(
                jnp.arange(cap, dtype=jnp.int32), mode="drop")
            # a duplicate key lost its slot to another row
            trip = jnp.bool_(False) if semi_like else \
                jnp.sum((table >= 0).astype(jnp.int32)) != \
                jnp.sum(bvalid.astype(jnp.int32))
        return table, trip

    @staticmethod
    def _direct_probe(probe, pk, pdata, kmin, table):
        """(bidx, ok) by direct address: one gather of the probe's
        capacity."""
        cap = table.shape[0]
        with jax.named_scope("probe"):
            off = _unsigned(pdata) - _unsigned(kmin)
            in_range = off < cap
            bidx = table.at[
                jnp.where(in_range, off, 0).astype(jnp.int32)
            ].get(mode="promise_in_bounds")
            ok = jnp.logical_and(
                jnp.logical_and(probe.live, pk.validity),
                jnp.logical_and(in_range, bidx >= 0))
            return jnp.maximum(bidx, 0), ok

    # -- a join chain: its later joins at the width of its live rows --------

    def _source_capacity(self, head) -> int:
        """The capacity of the source a chain's first join probes; 0 where
        it is not bound (the join then raises as it always did)."""
        src = _below_links(head.left)
        rid = src.resource_id if isinstance(src, P.FFIReader) else \
            self.scan_rids.get(id(src), "?")
        t = self.bindings.get(rid)
        return t.capacity if t is not None else 0

    def _join_chain(self, joins: List[P.BroadcastJoin]) -> DeviceTable:
        """A chain of K = 1 inner joins over a source (`join_chain`, top
        first).  Its first join keeps few of the source's rows as a rule,
        yet every later probe gathers at the source's full capacity, so the
        program counts the rows the first join left and chooses, per device
        (one `lax.switch`, no collective inside a side), the narrowest
        width of a short ladder that holds them (`_chain_rungs`):

        - a rung — those rows are brought to the front of a table that
          wide (`_compact_front`: stable, no sort), the later joins run
          there with their projections and filters, and their output goes
          on with dead rows behind it at the source's capacity, so nothing
          downstream changes shape;
        - otherwise — full: the later joins at the source's capacity, as
          without a chain.

        The first join runs before the choice, as it always did, and so do
        the later joins' build sides — their broadcasts are collectives —
        and build halves (`_chain_build_half`); inside a side each later
        join runs its probe half alone (`_chain_probe`).  Their guards'
        and counters' `psum`s sit after the choice."""
        from auron_tpu.exprs.typing import infer_type
        from auron_tpu.ops.joins.exec import join_output_schema
        top, head = joins[0], joins[-1]
        t = self.eval_node(head)
        schemas = {id(head): t.schema}
        later: Dict[int, _ChainJoin] = {}
        for j in reversed(joins[:-1]):
            label = self.labels.get(id(j), j.kind)
            # the top's own scope is open already
            with jax.named_scope(label) if j is not top else \
                    contextlib.nullcontext():
                build = self.eval_node(j.right)
                with jax.named_scope("build"):
                    bkeys = self._eval_keys(j.on.right_keys, build,
                                            "join key")
                probe_schema = _linked_schema(j.left, schemas)
                later[id(j)] = self._chain_build_half(
                    label, build, bkeys,
                    [infer_type(k, probe_schema) for k in j.on.left_keys])
            schemas[id(j)] = join_output_schema(
                probe_schema, build.schema, j.join_type,
                j.existence_output_name)
        n_live = jnp.sum(t.live.astype(jnp.int32))
        cap = t.capacity
        rungs = _chain_rungs(cap)

        def later_joins(head_out: Callable[[], DeviceTable]) -> DeviceTable:
            self._chain = (head, head_out, later)
            try:
                return self._chain_probe(top, later[id(top)])
            finally:
                self._chain = None

        def rung_side(width: int):
            def side():
                out = later_joins(
                    lambda: _compact_front(t, n_live, width))
                with jax.named_scope("compact"):
                    return _pad_rows((_as_words(out.cols), out.live), cap)
            return side

        def full_side():
            out = later_joins(lambda: t)
            return _as_words(out.cols), out.live

        # the narrowest width that holds the live rows; past them all, full
        chosen = sum((n_live > w).astype(jnp.int32) for w in rungs)
        with inside_branch():
            words, live = lax.switch(
                chosen, [rung_side(w) for w in rungs] + [full_side])
        cols = _as_bytes(words)
        for c in later.values():
            self._trip_guard(c.trip, False)
            self.probes.append((c.label, None if c.dense is None else
                                lax.psum(c.dense.astype(jnp.int32),
                                         self.axis)))
        self.join_chains.append((
            {"label": self.labels.get(id(head), head.kind),
             "capacity": cap * self.n_dev, "widths": (*rungs, cap)},
            self._choice_counts(chosen, n_live, len(rungs))))
        return DeviceTable(schemas[id(top)], cols, live)

    def _chain_build_half(self, label: str, build: DeviceTable, bkeys,
                          ptypes: List[DataType]) -> _ChainJoin:
        """What a later join of a chain computes from its build side alone,
        once, before the chain's choice: where the keys are directly
        addressable by type, the key range, the choice between the probes
        (`dense`), and under one `lax.cond` on it the direct table or the
        sorted build hashes — the other side's arrays stand in as
        placeholders of their shapes; otherwise the sorted build hashes.
        The duplicate-key trip depends on the build side alone, so it is
        computed here too."""
        if not _direct_key_types(ptypes, bkeys):
            with jax.named_scope("build"):
                order, sorted_bh = self._sorted_build_hashes(build, bkeys)
                trip = self._search_trip(bkeys, order, sorted_bh, False)
            return _ChainJoin(label, build, bkeys, (order, sorted_bh), trip,
                              None)
        [bk] = bkeys
        bdata = _key_words(bk.data)
        bvalid, kmin, dense = self._key_range(build, bk, bdata)
        cap = build.capacity

        def direct():
            table, trip = self._direct_table(build, bvalid, bdata, kmin,
                                             False)
            return (table, jnp.zeros(cap, jnp.int32),
                    jnp.zeros(cap, jnp.uint64), trip)

        def search():
            with jax.named_scope("build"):
                order, sorted_bh = self._sorted_build_hashes(build, bkeys)
                trip = self._search_trip(bkeys, order, sorted_bh, False)
            return jnp.full(cap, -1, jnp.int32), order, sorted_bh, trip

        table, order, sorted_bh, trip = lax.cond(dense, direct, search)
        return _ChainJoin(label, build, bkeys, (kmin, table, order, sorted_bh),
                          trip, dense)

    def _chain_probe(self, n: P.BroadcastJoin, c: _ChainJoin) -> DeviceTable:
        """A later join of a chain inside a side of its choice: its probe
        side at that side's width, the probe half of its lookup and its
        output."""
        probe = self.eval_node(n.left)
        with jax.named_scope("probe"):
            pkeys = self._eval_keys(n.on.left_keys, probe, "join key")
            if _direct_addressable(pkeys, c.bkeys) != (c.dense is not None):
                # the build half was traced for the keys' types as the
                # plan's schemas give them
                raise SpmdUnsupported(
                    f"join chain: {c.label}'s probe keys are not of the "
                    "types their schema gives")
            bidx, ok = self._chain_lookup(probe, pkeys, c)
            return self._join_emit(probe, c.build, bidx, ok, n.join_type,
                                   n.existence_output_name,
                                   take_build=_take_rows)

    def _chain_lookup(self, probe, pkeys, c: _ChainJoin):
        """(bidx, ok) from a build half: the direct gather, or the search
        and the exact-key filter, under the same `lax.cond` on `dense`
        where the build half has one."""
        def search():
            ph = self._probe_hashes(probe, pkeys)
            return self._search_probe(c.build, pkeys, c.bkeys,
                                      *c.half[-2:], ph)
        if c.dense is None:
            return search()
        kmin, table = c.half[:2]
        [pk] = pkeys
        return lax.cond(
            c.dense,
            lambda: self._direct_probe(probe, pk, _key_words(pk.data), kmin,
                                       table),
            search)

    def _join_emit(self, probe, build, bidx, ok, join_type,
                   existence_name, take_build=None):
        """The join's output from the lookup: `ok` marks the probe rows
        that found their key, `bidx` the build row each found (each build
        column's through `c.gather`, or `take_build`)."""
        from auron_tpu.ops.joins.exec import join_output_schema
        schema = join_output_schema(probe.schema, build.schema, join_type,
                                    existence_name)
        if join_type in ("left_semi", "left_anti"):
            keep = ok if join_type == "left_semi" \
                else jnp.logical_not(ok)
            return DeviceTable(schema, list(probe.cols),
                               jnp.logical_and(probe.live, keep))
        if join_type == "existence":
            exists = DeviceColumn(
                DataType.bool_(), jnp.logical_and(ok, probe.live),
                jnp.ones(probe.capacity, bool))
            return DeviceTable(schema, list(probe.cols) + [exists],
                               probe.live)
        bcols = [take_build(c, bidx, ok) if take_build else
                 c.gather(bidx, ok) for c in build.cols]
        out_cols = list(probe.cols) + bcols
        if join_type in ("full", "right"):
            live1 = probe.live if join_type == "full" \
                else jnp.logical_and(probe.live, ok)
            return self._join_outer_tail(schema, probe, build, out_cols,
                                         ok, bidx, live1)
        live = jnp.logical_and(probe.live, ok) if join_type == "inner" \
            else probe.live
        return DeviceTable(schema, out_cols, live)

    def _compact_live(self, t: DeviceTable, new_cap: int) -> DeviceTable:
        """Stable-compact live rows to the front and cut capacity to
        new_cap (a join-guard trips past it -> compaction-off retry).
        Applied after K-expanded joins so a JOIN CHAIN stays near the
        original probe capacity instead of growing K-fold per join
        (q85r's 5-join chain at K=4 otherwise pays 4^5 = 1024x row
        capacity — measured 107s warm for 10 output rows).  The stable
        sort preserves live-row order, so per-device limit prefixes are
        unchanged."""
        if not self.join_compact or new_cap >= t.capacity:
            return t
        with jax.named_scope("compact"):
            n_live = jnp.sum(t.live.astype(jnp.int32))
            self.join_guards.append(
                lax.psum((n_live > new_cap).astype(jnp.int32),
                         self.axis) > 0)
            perm = _live_first_perm(t.live)[:new_cap]
            ok = jnp.take(t.live, perm)
            cols = [c.gather(perm, ok) for c in t.cols]
            return DeviceTable(t.schema, cols, ok)

    def _join_expanded(self, probe, build, pkeys, bkeys, order,
                       sorted_bh, ph, join_type, existence_name, K: int):
        """K-way pair expansion: every probe row probes its full hash
        range [lo, hi), emitting up to K pairs (static output capacity
        probe.cap * K).  Ranges wider than K trip a runtime guard and
        the driver falls back to the serial engine — the static-shape
        answer to the reference's dynamic pair batches
        (joins/bhj/full_join.rs)."""
        from auron_tpu.ops.joins.exec import join_output_schema
        cap = probe.capacity
        capk = cap * K
        lo = jnp.searchsorted(sorted_bh, ph, side="left") \
            .astype(jnp.int32)
        hi = jnp.searchsorted(sorted_bh, ph, side="right") \
            .astype(jnp.int32)
        count = hi - lo
        over = jnp.any(jnp.logical_and(probe.live, count > K))
        self.guards.append(
            lax.psum(over.astype(jnp.int32), self.axis) > 0)
        i = (jnp.arange(capk, dtype=jnp.int32) // K)
        j = jnp.arange(capk, dtype=jnp.int32) % K
        allv = jnp.ones(capk, bool)
        pair_has = j < jnp.minimum(jnp.take(count, i), K)
        bpos = jnp.clip(jnp.take(lo, i) + j, 0, build.capacity - 1)
        bidx = jnp.take(order, bpos)
        probe_live_r = jnp.take(probe.live, i)
        pkeys_r = [k.gather(i, allv) for k in pkeys]
        ok = self._exact_eq(pkeys_r, bkeys, bidx,
                            jnp.logical_and(pair_has, probe_live_r))
        matched_any = jnp.any(ok.reshape(cap, K), axis=1)
        schema = join_output_schema(probe.schema, build.schema, join_type,
                                    existence_name)
        probe_cols_r = [c.gather(i, allv) for c in probe.cols]
        bcols = [c.gather(bidx, ok) for c in build.cols]
        out_cols = probe_cols_r + bcols
        # unmatched probe rows emit exactly once (their j==0 slot)
        emit_unmatched = jnp.logical_and(
            jnp.logical_and(j == 0, probe_live_r),
            jnp.logical_not(jnp.take(matched_any, i)))
        # compact back to the pre-expansion capacity (join-guarded; a
        # genuine fan-out past it retries with compaction off)
        if join_type == "inner":
            return self._compact_live(
                DeviceTable(schema, out_cols, ok), cap)
        if join_type == "left":
            return self._compact_live(
                DeviceTable(schema, out_cols,
                            jnp.logical_or(ok, emit_unmatched)),
                cap)
        # full / right: the outer tail appends build.capacity unmatched
        # slots, so the target must cover probe + build rows
        live1 = jnp.logical_or(ok, emit_unmatched) \
            if join_type == "full" else ok
        return self._compact_live(
            self._join_outer_tail(schema, probe, build, out_cols, ok,
                                  bidx, live1),
            bucket_capacity(cap + build.capacity))

    # sort / limit -------------------------------------------------------
    #
    # SPMD operator bodies are order-insensitive (hash agg, hash join,
    # exchanges); ordering only matters at the driver-side emission, which
    # the peeled host tail re-establishes.  A mid-plan Sort with no fetch
    # limit is therefore a no-op here; one WITH a fetch limit is a
    # per-device top-k MASK (rows keep their positions, losers go dead —
    # the sort_exec.rs:86 FetchLimit analogue), skipped entirely when the
    # host tail's global sort shadows it (same key prefix, limit at least
    # as strict).

    def _do_sort(self, n: P.Sort) -> DeviceTable:
        from auron_tpu.ops.sort_keys import (
            encode_sort_keys, lexsort_indices_live,
        )
        if n.fetch_limit is None:
            return self.eval_node(n.child)
        s = self.shadow_sort
        if s is not None and s.fetch_limit is not None and \
                s.fetch_limit <= n.fetch_limit and \
                s.sort_exprs == n.sort_exprs[:len(s.sort_exprs)]:
            return self.eval_node(n.child)
        t = self.eval_node(n.child)
        keys = self._eval_keys(tuple(x.child for x in n.sort_exprs), t,
                               "sort key")
        orders = tuple((x.asc, x.nulls_first) for x in n.sort_exprs)
        words = encode_sort_keys(keys, orders)
        perm = lexsort_indices_live(words, t.live)
        rank = jnp.zeros(t.capacity, jnp.int32).at[perm].set(
            jnp.arange(t.capacity, dtype=jnp.int32))
        live = jnp.logical_and(t.live, rank < n.fetch_limit)
        return DeviceTable(t.schema, t.cols, live)

    def _do_limit(self, n: P.Limit) -> DeviceTable:
        # per-device limit+offset over the device's row order — exactly
        # the serial engine's per-partition stream semantics
        # (limit_exec.rs:42); the global CollectLimit shape puts a single
        # exchange + final limit above this.  A Sort anywhere below makes
        # the prefix ORDER-dependent (serial takes the sorted prefix; the
        # SPMD sort is a no-op/mask that leaves rows in place) — reject
        # so the serial engine computes the correct sorted prefix.
        for node in _walk_native(n.child, self):
            if node.kind == "sort":
                raise SpmdUnsupported(
                    "limit over a sorted input is order-sensitive")
        t = self.eval_node(n.child)
        live_rank = jnp.cumsum(t.live.astype(jnp.int32))  # 1-based
        live = jnp.logical_and(
            t.live, jnp.logical_and(live_rank > n.offset,
                                    live_rank <= n.offset + n.limit))
        return DeviceTable(t.schema, t.cols, live)

    # window -------------------------------------------------------------

    def _do_window(self, n: P.Window) -> DeviceTable:
        from auron_tpu.ops.sort_keys import (
            encode_sort_keys, lexsort_indices_live,
        )
        from auron_tpu.ops.window.exec import (
            _coerce_to, _default_window_type, compute_window_fn,
            group_limit_rank, segment_context,
        )
        if not _window_ok(n, self.exchanges):
            raise SpmdUnsupported(
                "window needs a colocating exchange (hash on a subset of "
                "its partition keys, or single) under it")
        # unsupported window fns surface as NotImplementedError from
        # compute_window_fn below — wrapped into SpmdUnsupported there,
        # so the supported set lives in ONE place (ops/window/exec.py)
        t = self.eval_node(n.child)
        cap = t.capacity
        pcols = self._eval_keys(n.partition_by, t, "window argument")
        ocols = self._eval_keys(tuple(s.child for s in n.order_by), t,
                                "window argument")
        args_u = [self._eval_keys(
            tuple(wf.args) + ((wf.agg.children if wf.agg else ())), t,
            "window argument")
            for wf in n.window_funcs]
        orders = tuple((s.asc, s.nulls_first) for s in n.order_by)
        pwords = encode_sort_keys(
            pcols, tuple((True, True) for _ in n.partition_by))
        owords = encode_sort_keys(ocols, orders)
        perm = lexsort_indices_live(pwords + owords, t.live)
        allv = jnp.ones(cap, bool)
        sorted_cols = [c.gather(perm, allv) for c in t.cols]
        sorted_args = [[a.gather(perm, allv) for a in args]
                       for args in args_u]
        n_live = jnp.sum(t.live.astype(jnp.int32))
        live = jnp.arange(cap, dtype=jnp.int32) < n_live
        sp = [jnp.take(w, perm) for w in pwords]
        so = [jnp.take(w, perm) for w in owords]

        # segment structure + per-fn kernels: the SAME helpers the serial
        # operator runs (single source of truth for boundary semantics)
        c = segment_context(sp, so, live, cap)
        out_cols = []
        for wf, args in zip(n.window_funcs, sorted_args):
            try:
                out_cols.append(_coerce_to(
                    wf, compute_window_fn(wf, args, c, n.order_by)))
            except NotImplementedError as e:
                raise SpmdUnsupported(str(e)) from e
        fields = list(t.schema.fields)
        cols = list(sorted_cols)
        if n.output_window_cols:
            cols += out_cols
            fields += [Field(wf.name or wf.fn,
                             wf.return_type or _default_window_type(wf))
                       for wf in n.window_funcs]
        if n.group_limit is not None:
            live = jnp.logical_and(
                live, group_limit_rank(n.group_limit.rank_fn, c)
                <= n.group_limit.k)
        return DeviceTable(Schema(tuple(fields)), cols, live)


def _feeding_exchange(node, exchanges):
    """The exchange Partitioning feeding `node`, looking through
    row-preserving pass-through ops (coalesce/debug); None otherwise."""
    child = node.child
    while isinstance(child, (P.CoalesceBatches, P.Debug)):
        child = child.child
    if isinstance(child, P.IpcReader) and child.resource_id in exchanges:
        return exchanges[child.resource_id].partitioning
    return None


def _colocating(part, keys) -> bool:
    """True when `part` guarantees rows with equal `keys` land on one
    device: a single-partition exchange, or a hash exchange whose
    expressions are a subset of `keys`."""
    if part is None:
        return False
    if part.mode == "single":
        return True
    if part.mode == "hash":
        ks = set(keys)
        return all(e in ks for e in (part.expressions or ()))
    return False


def _single_agg_ok(agg, exchanges) -> bool:
    """A single-mode agg is per-partition; in SPMD the device is the
    partition.  Admit it only when the exchange feeding it guarantees
    per-device groups are complete (colocating for its grouping keys),
    or — for an UNGROUPED agg — any exchange (per-partition global rows,
    the engine's per-partition contract)."""
    part = _feeding_exchange(agg, exchanges)
    if part is None:
        return False
    if _colocating(part, agg.grouping):
        return True
    if part.mode == "round_robin":
        return not agg.grouping
    return False


def _key_positions(part, keys):
    """The index set of `keys` a partitioning hashes on, or None when it
    gives no colocation guarantee for `keys`.  single -> empty set (all
    rows funnel to one device)."""
    if part is None:
        return None
    if part.mode == "single":
        return frozenset()
    if part.mode != "hash" or not part.expressions:
        return None
    keys = list(keys)
    try:
        return frozenset(keys.index(e) for e in part.expressions)
    except ValueError:
        return None


def _side_positions(node, keys, exchanges):
    """Colocation guarantee of one join side for `keys`, looked through
    distribution-preserving operators: fetch-less sorts, coalesce/debug,
    filters (row drops don't move rows), grouped aggs (a group's row
    stays where its exchange put the inputs; the feeding exchange's
    expressions name the agg's output attributes in the canonical
    partial/exchange/final shape), and joins (output rows keep the probe
    side's placement; pl == pr makes the build's appended rows agree)."""
    while True:
        if isinstance(node, (P.CoalesceBatches, P.Debug, P.Filter)):
            node = node.child
            continue
        if isinstance(node, P.Sort) and node.fetch_limit is None:
            node = node.child
            continue
        break
    if isinstance(node, P.IpcReader) and node.resource_id in exchanges:
        return _key_positions(exchanges[node.resource_id].partitioning,
                              keys)
    if isinstance(node, P.Agg):
        return _key_positions(_feeding_exchange(node, exchanges), keys)
    if isinstance(node, (P.HashJoin, P.SortMergeJoin)):
        return _side_positions(node.left, keys, exchanges)
    if isinstance(node, P.BroadcastJoin):
        probe = node.left if node.broadcast_side == "right" else node.right
        return _side_positions(probe, keys, exchanges)
    return None


def _smj_colocated(n, exchanges) -> bool:
    """Equal join keys must land on one device: both sides carry the
    same positional hash-key guarantee (so the partition hashes agree
    row-for-row), or both funnel through single exchanges."""
    pl = _side_positions(n.left, tuple(n.on.left_keys), exchanges)
    pr = _side_positions(n.right, tuple(n.on.right_keys), exchanges)
    return pl is not None and pl == pr


def _window_ok(win, exchanges) -> bool:
    """Window partitions must be device-complete: the feeding exchange
    must colocate the PARTITION BY keys (no partition keys -> only a
    single exchange qualifies)."""
    return _colocating(_feeding_exchange(win, exchanges),
                       win.partition_by)




def _require_native(node) -> P.PlanNode:
    if not isinstance(node, P.PlanNode):
        raise SpmdUnsupported("foreign subtree inside SPMD stage")
    return node


from auron_tpu.ir.node import tree_has_kind as _tree_has  # noqa: E402


# ---------------------------------------------------------------------------
# host driver: shard inputs, run the program, gather + compact
# ---------------------------------------------------------------------------

def _rows_per_device(n: int, n_dev: int) -> List[int]:
    """How `_shard_table` deals n rows in file order: ceil(n / n_dev) a
    device until they run out."""
    per_dev = -(-max(n, 1) // n_dev)
    return [min(max(n - d * per_dev, 0), per_dev) for d in range(n_dev)]


def _shard_table(table, mesh: Mesh, axis: str) -> Tuple[Schema, List[Any],
                                                        Array, int]:
    """Split an arrow table row-wise across the mesh: returns flat arrays
    of shape [n_dev*cap] (to be sharded along the axis) + live mask.  The
    columns' leaves are numpy arrays, built on the host at their final
    shape: `jax.device_put` under the mesh's sharding is their one
    crossing, each device's rows straight to it."""
    from auron_tpu.columnar.arrow_interop import arrow_array_to_host_column
    from auron_tpu.ir.schema import from_arrow_schema
    n_dev = int(np.prod([mesh.shape[a] for a in axis])) \
        if isinstance(axis, tuple) else mesh.shape[axis]
    dealt = _rows_per_device(table.num_rows, n_dev)
    cap = bucket_capacity(max(dealt))
    schema = from_arrow_schema(table.schema)
    cols: List[Any] = []
    for f, arr in zip(schema, table.columns):
        col = arrow_array_to_host_column(f.dtype, arr, cap, dealt,
                                         wide=True)
        if isinstance(col, HostColumn):
            raise SpmdUnsupported("host-resident column in SPMD source")
        cols.append(col)
    live = np.zeros(n_dev * cap, bool)
    for d, got in enumerate(dealt):
        live[d * cap: d * cap + got] = True
    return schema, cols, jnp.asarray(live), cap


# ---------------------------------------------------------------------------
# device-resident source shard cache (round-4: kill the per-execute
# re-materialize / re-pad / re-device_put cost that made the stage path
# lose to serial at bench scale — the reference's hot path does zero
# per-batch host work, rt.rs:141-238)
# ---------------------------------------------------------------------------

import collections  # noqa: E402
import weakref  # noqa: E402


def _mesh_fingerprint(mesh: Mesh) -> Tuple:
    devs = list(np.asarray(mesh.devices).flat)
    return (tuple(mesh.shape.items()),
            tuple((d.platform, d.id) for d in devs))


def _string_cfg_fingerprint() -> Tuple:
    from auron_tpu.config import conf as _conf
    return (int(_conf.get("auron.string.device.max.width")),
            str(_conf.get("auron.string.width.buckets")))


class _ByteBudgetLRU:
    """Byte-bounded LRU map: key -> (value, nbytes).  A store evicts
    least-recently-used entries until the bytes fit the budget, but never
    an entry the execute in flight reads: the caller hands `_store` the
    keys of its attempt's sources (`reads`: those it has been served,
    has stored or is about to store), and the budget evicts among the
    others only.  What one query reads is the
    floor: a query reads several sources, and were one of them alone past
    the budget, keeping just the newest entry would have every store
    evict the query's other sources and every execute read all of them
    again, forever.  If what the attempt reads is over the budget by
    itself, it all stays (`over_budget_bytes`) until another query's
    stores evict it, least recently used first.  Subclasses supply the
    budget and layer their keying semantics on top."""

    def __init__(self):
        self._entries: "collections.OrderedDict[Any, Tuple[Any, int]]" = \
            collections.OrderedDict()
        self._bytes = 0

    def _budget(self) -> int:  # pragma: no cover - abstract
        raise NotImplementedError

    def _lookup(self, key):
        if self._entries and self._budget() <= 0:
            # budget lowered to 0 ("disables"): release everything —
            # serving retained entries would keep their device buffers
            # alive past the user's memory-pressure request
            self.clear()
            return None
        e = self._entries.get(key)
        if e is None:
            return None
        self._entries.move_to_end(key)
        return e[0]

    def _evict_key(self, key) -> None:
        e = self._entries.pop(key, None)
        if e is not None:
            self._bytes -= e[1]

    def _store(self, key, value, nbytes: int,
               reads: Collection = ()) -> int:
        """Returns how many entries the budget evicted (a disabled cache
        stores nothing and evicts nothing)."""
        budget = self._budget()
        if budget <= 0:
            return 0
        self._evict_key(key)
        self._entries[key] = (value, nbytes)
        self._bytes += nbytes
        evicted = 0
        if self._bytes > budget:
            for old_key in [k for k in self._entries
                            if k != key and k not in reads]:
                if self._bytes <= budget:
                    break
                self._evict_key(old_key)
                self._dropped(old_key)
                evicted += 1
        return evicted

    def held_bytes(self) -> int:
        return self._bytes

    def over_budget_bytes(self) -> int:
        """Bytes held past the budget: what an execute's own reads kept
        there (0 where the cache is disabled: it holds nothing)."""
        return max(0, self._bytes - max(0, self._budget()))

    def _dropped(self, key) -> None:
        """Hook: called for keys evicted by the byte budget."""

    def values(self) -> List[Any]:
        """The cached values, least recently used first."""
        return [v for v, _nbytes in self._entries.values()]

    def clear(self) -> None:
        self._entries.clear()
        self._bytes = 0


class _DeviceShardCache(_ByteBudgetLRU):
    """LRU cache of sharded, device-resident source tables.

    pyarrow Tables are immutable, so `id(table)` is a sound content key
    while the table object is alive; a weakref finalizer evicts every
    entry for a table the moment it is garbage collected (no stale-id
    reuse window).  Entries are bounded by device bytes
    (auron.spmd.source.cache.mb), except for what the execute in flight
    reads (`_ByteBudgetLRU`); eviction drops the JAX array
    references and XLA frees the buffers once no running program holds
    them."""

    def __init__(self):
        super().__init__()
        self._tid_keys: Dict[int, set] = {}

    def _budget(self) -> int:
        from auron_tpu.config import conf as _conf
        return int(_conf.get("auron.spmd.source.cache.mb")) << 20

    def _dropped(self, key) -> None:
        self._tid_keys.get(key[0], set()).discard(key)

    def _evict_tid(self, tid: int) -> None:
        for key in self._tid_keys.pop(tid, ()):
            self._evict_key(key)

    # the shard key (mesh/axis/string-config) is threaded through
    # explicitly: a process-global "current key" would interleave under
    # two concurrent sessions on different meshes and serve shards placed
    # for the other run's mesh (ADVICE r4)

    def get(self, table, shard_key: Tuple) -> Optional[dict]:
        key = self.key_of(table, shard_key)
        e = self._lookup(key)
        if e is None or e["ref"]() is not table:
            return None
        return e

    @staticmethod
    def key_of(table, shard_key: Tuple) -> Tuple:
        return (id(table), *shard_key)

    def put(self, table, entry: dict, shard_key: Tuple,
            reads: Collection = ()) -> int:
        """Stores the entry; returns how many entries that evicted.
        `reads`: the keys (`key_of`) of the attempt's sources, which the
        budget does not evict."""
        tid = id(table)
        key = self.key_of(table, shard_key)
        nbytes = sum(
            int(getattr(x, "nbytes", 0))
            for x in jax.tree.leaves((entry["cols"], entry["live"])))
        entry["ref"] = weakref.ref(
            table, lambda _r, tid=tid: self._evict_tid(tid))
        evicted = self._store(key, entry, nbytes, reads)
        if key in self._entries:
            self._tid_keys.setdefault(tid, set()).add(key)
        return evicted

    def clear(self) -> None:
        super().clear()
        self._tid_keys.clear()


_DEVICE_SHARDS = _DeviceShardCache()


def _scan_files_fp(node) -> Optional[Tuple]:
    """(path, mtime_ns, size) for every file under a scan node; None when
    any file is unstattable (such scans never cache)."""
    import os
    fp = []
    for g in getattr(node, "file_groups", ()) or ():
        for p in getattr(g, "paths", ()) or ():
            try:
                st = os.stat(p)
            except OSError:
                return None
            fp.append((p, st.st_mtime_ns, st.st_size))
    return tuple(fp)


class _ScanTableCache(_ByteBudgetLRU):
    """LRU cache of materialized scan leaves keyed by (scan node, file
    stat fingerprint): repeat executes of the same query re-read nothing
    from disk unless a file's (mtime_ns, size) changed.  The fingerprint
    is taken BEFORE the scan reads (no stat-after-read TOCTOU: a file
    rewritten mid-read changes the fingerprint the next get computes, so
    the stale entry never matches).  Bounded by arrow bytes
    (auron.spmd.scan.cache.mb), except for what the execute in flight
    reads (`_ByteBudgetLRU`)."""

    def _budget(self) -> int:
        from auron_tpu.config import conf as _conf
        return int(_conf.get("auron.spmd.scan.cache.mb")) << 20

    def get(self, node, fp: Optional[Tuple]):
        if fp is None:
            return None
        return self._lookup((node, fp))

    def put(self, node, fp: Optional[Tuple], table,
            reads: Collection = ()) -> int:
        """Stores the table; returns how many entries that evicted.
        `reads`: the keys (node, fingerprint) of the attempt's leaves,
        which the budget does not evict."""
        if fp is None:
            return 0
        return self._store((node, fp), table, int(table.nbytes), reads)


_SCAN_TABLES = _ScanTableCache()


def clear_source_caches() -> None:
    """Drop all cached scan tables and device-resident shards (tests and
    memory-pressure handling)."""
    _DEVICE_SHARDS.clear()
    _SCAN_TABLES.clear()


def execute_plan_spmd(plan: P.PlanNode, conv_ctx, mesh: Mesh,
                      source_tables: Dict[str, Any], axis: str = "parts",
                      stats: Optional[Dict[str, Any]] = None):
    """Compile + run `plan` as one shard_map program over `mesh`.

    source_tables: rid -> pyarrow.Table for every FFI source the plan
    references (the C2N boundary inputs).  Returns a pyarrow.Table.
    Raises SpmdUnsupported when the plan shape cannot be expressed.
    `stats`, when given, receives what the last attempt's program
    reported of itself (the attempt that gave the result, or the one whose
    guard tripped last): `join_probes`, {operator label: "direct" |
    "search" | "direct k/n"} for every K=1 join; `agg_inputs`, {operator
    label: input "compact" | "full" | "compact k/n", the width its body
    ran at, live rows, capacity} for every aggregate that chose and
    `join_chains`, {its first join's label: chain "compact" | "full" |
    "compact k/n", the width its later joins ran at, live rows after the
    first join, capacity} for every join chain that chose
    (`_width_marks`); `segments`, the
    segment `bounds` derived in the program's trace and the `reductions`
    that took them; over more than one device also `exchanges` and
    `broadcasts`, {operator label: counts} for every boundary
    (`_crossing_stats`), and `sources`, {canonical rid: rows, cap, rows
    on the fullest and the emptiest device}.  `ingest`
    holds what the scan leaves' tasks read (`INGEST_COUNTS`) and `shard`
    what was placed on the device (`SHARD_COUNTS`), each with the state of
    its source cache (`CACHE_STATE`): summed over the attempts, but the
    caches' bytes as the last attempt left them.

    A tripped join guard (duplicate build keys past the current match
    factor) retries ONCE with auron.spmd.join.match.factor pair
    expansion before giving up — multi-match joins pay the K-wide
    buffers only when the data actually needs them.  The factor that
    succeeded is remembered per (canonical program, mesh, configured k),
    so repeat executes of a duplicate-key query start at the right width
    instead of paying the trip-then-retry double execution every time;
    the config in the key means re-tuning the factor drops stale hints
    (a hint only ever widens buffers — correctness never depends on it).
    """
    from auron_tpu.config import conf as _conf
    # canonicalize ONCE: the hint lookup, program cache and tracer all
    # run on the rewritten (rid-token) views
    plan, conv_ctx, source_tables = _canonicalize_rids(
        plan, conv_ctx, source_tables)
    k = int(_conf.get("auron.spmd.join.match.factor"))
    hint_key = (
        plan,
        tuple(sorted((rid, job.child, job.partitioning)
                     for rid, job in conv_ctx.exchanges.items())),
        tuple(sorted((rid, job.child)
                     for rid, job in conv_ctx.broadcasts.items())),
        tuple(mesh.shape.items()), k)
    match = _MATCH_FACTOR_HINT.get(hint_key, 1)
    # agg-shrink capacity LADDER: start at the configured hint; each
    # overflow retries 4x wider (x16 max) before giving up the shrink
    # entirely — a high-cardinality agg (q21i at sf10: 1M groups/device)
    # then lands on a 1M-row buffer instead of reverting every
    # downstream op to full input capacity (the 135GB OOM shape).  The
    # key embeds the CONFIGURED cap so re-tuning it restarts the ladder.
    cap_hint = int(_conf.get("auron.spmd.agg.capacity.hint"))
    shrink_key = (hint_key, cap_hint)
    cap_eff = _SHRINK_HINT.get(shrink_key, cap_hint)
    # the hard-fail hint embeds the configs that size the hard guard
    # (quota margin + configured cap): re-tuning either restarts the
    # hard-climb eligibility, same discipline as the shrink ladder
    hard_key = (hint_key, cap_hint,
                float(_conf.get("auron.spmd.exchange.quota.margin")))
    join_compact = bool(_conf.get("auron.spmd.join.compact.enable")) \
        and not _JOIN_COMPACT_OFF_HINT.get(hint_key, False)
    # bounded retries across the independent guard dimensions (match
    # factor, shrink ladder, join compaction); hints remember the
    # working combination per canonical program so repeat executes skip
    # the trip-then-retry runs
    from auron_tpu.faults import InjectedDeviceFault
    from auron_tpu.runtime import retry as _retry
    device_budget = max(0, _retry.RetryPolicy.from_conf().max_attempts - 1)
    for _attempt in range(6):
        try:
            out = _execute_plan_spmd_once(plan, conv_ctx, mesh,
                                          source_tables, axis,
                                          match_factor=match,
                                          agg_cap_hint=cap_eff,
                                          join_compact=join_compact,
                                          stats=stats)
            if match > 1:
                _MATCH_FACTOR_HINT[hint_key] = match
            if cap_eff != cap_hint:
                _SHRINK_HINT[shrink_key] = cap_eff
            if bool(_conf.get("auron.spmd.join.compact.enable")) and \
                    not join_compact:
                _JOIN_COMPACT_OFF_HINT[hint_key] = True
            return out
        except InjectedDeviceFault as e:
            # device-fault tier: re-execute the stage program a bounded
            # number of times, then DEGRADE — raise SpmdUnsupported so
            # the session falls back to the serial per-partition path
            # (the session counts the fallback)
            if device_budget > 0:
                device_budget -= 1
                _retry.add_retry()
                continue
            raise SpmdUnsupported(
                f"device fault persisted past the retry budget: {e}"
            ) from e
        except SpmdGuardTripped as e:
            if e.join_compact and join_compact:
                join_compact = False
                _retry.add_retry()
                continue
            # the climb exists because post-agg exchange quotas are sized
            # from the SHRUNK capacity — a plan with no Agg anywhere was
            # never shrunk, so its hard trip is genuine (skew/dup keys)
            # and climbing would only re-execute a failing program 4 more
            # times before the serial fallback
            has_agg = any(isinstance(nn, P.Agg)
                          for nn in _walk_native(plan, conv_ctx))
            hard_climb = (e.hard and cap_eff > 0 and has_agg and
                          not _HARD_FAIL_HINT.get(hard_key, False))
            if (e.shrink or hard_climb) and cap_eff > 0:
                # hard trips climb too: post-agg exchange quotas are
                # sized from the SHRUNK capacity, so a routing skew that
                # fit pre-shrink can overflow the hard guard — the
                # ladder must get to try wider rungs (-> shrink off =
                # pre-shrink sizing) before falling back to serial.  A
                # genuine dup-key failure survives every rung; the hint
                # below makes repeat executes skip the climb entirely.
                cap_eff = cap_eff * 4 \
                    if cap_eff < cap_hint * 16 else 0
                _retry.add_retry()
                continue
            if e.retryable and match == 1 and k > 1:
                match = k
                _retry.add_retry()
                continue
            if e.hard:
                _HARD_FAIL_HINT[hard_key] = True
            raise
    raise SpmdGuardTripped("guard retries exhausted")


def _canonicalize_rids(plan, conv_ctx, source_tables):
    """Rewrite every `resource_id` in the plan/exchange/broadcast trees to
    a deterministic walk-order token ("#0", "#1", ...), returning
    (plan, shim_ctx, source_tables) with all three views rekeyed
    consistently.  Plans from different conversions of the same query then
    compare (and hash) equal, which is what the compiled-program cache
    keys on."""
    import dataclasses
    from types import SimpleNamespace

    exchanges = getattr(conv_ctx, "exchanges", None) or {}
    broadcasts = getattr(conv_ctx, "broadcasts", None) or {}
    mapping: Dict[str, str] = {}

    def tok(rid: str) -> str:
        got = mapping.get(rid)
        if got is None:
            got = mapping[rid] = f"#{len(mapping)}"
        return got

    def canon_val(v):
        if dataclasses.is_dataclass(v) and not isinstance(v, type) and \
                type(v).__module__ == P.__name__:
            return canon(v)
        if isinstance(v, tuple):
            vals = tuple(canon_val(x) for x in v)
            if any(a is not b for a, b in zip(vals, v)):
                return vals
        return v

    # fields that hold ConvertContext-minted ids (per-query uuid inside):
    # resource_id names exchange/broadcast/source blocks; the bhm cache
    # ids key the SERIAL engine's build-table registry, which the SPMD
    # tracer never consults — both are name-independent here
    _RID_FIELDS = ("resource_id", "cache_id", "cached_build_hash_map_id")

    # memoized by identity: shared subtrees MUST stay shared — the union
    # collapse (and any other id()-based dedup) distinguishes "same child
    # referenced per partition" from "distinct children", and a rebuild
    # that forks a shared node would replicate its rows
    memo: Dict[int, Any] = {}

    def canon(node):
        got = memo.get(id(node))
        if got is not None:
            return got
        changes = {}
        for f in dataclasses.fields(node):
            v = getattr(node, f.name)
            nv = tok(v) if f.name in _RID_FIELDS and v else canon_val(v)
            if nv is not v:
                changes[f.name] = nv
        out = dataclasses.replace(node, **changes) if changes else node
        memo[id(node)] = out
        return out

    new_plan = canon(plan)
    # boundary jobs in token-discovery order; a job's child may reference
    # further exchanges (chained stages), so iterate to a fixed point
    new_ex: Dict[str, Any] = {}
    new_bc: Dict[str, Any] = {}
    done: set = set()
    while True:
        pending = [r for r in mapping if r not in done]
        if not pending:
            break
        for rid in pending:
            done.add(rid)
            if rid in exchanges:
                job = exchanges[rid]
                new_ex[mapping[rid]] = dataclasses.replace(
                    job, rid=mapping[rid],
                    child=canon(job.child)
                    if isinstance(job.child, P.PlanNode) else job.child)
            elif rid in broadcasts:
                job = broadcasts[rid]
                new_bc[mapping[rid]] = dataclasses.replace(
                    job, rid=mapping[rid],
                    child=canon(job.child)
                    if isinstance(job.child, P.PlanNode) else job.child)
    new_sources = {}
    for rid in sorted(source_tables):
        new_sources[mapping[rid] if rid in mapping else tok(rid)] = \
            source_tables[rid]
    shim = SimpleNamespace(exchanges=new_ex, broadcasts=new_bc,
                           sources=getattr(conv_ctx, "sources", {}))
    return new_plan, shim, new_sources


# last execute's device->host gather footprint (the IT runner and bench
# record this per query: VERDICT r4 ask #2 "gather bytes logged")
GATHER_STATS = {"bytes": 0, "rows": 0, "capacity": 0}

_SLICER_CACHE: Dict[Tuple, Any] = {}


def _gather_slicer(mesh: Mesh, axis, K: int, out_cols, out_live):
    """Cached shard_map program slicing every output leaf to its shard's
    first K rows — the device-side half of the two-phase compact gather."""
    key = (_mesh_fingerprint(mesh),
           axis if not isinstance(axis, tuple) else tuple(axis), K,
           tuple((str(x.dtype), x.shape)
                 for x in jax.tree.leaves((out_cols, out_live))))
    got = _SLICER_CACHE.get(key)
    if got is None:
        def body(cols, live):
            return (jax.tree.map(lambda a: a[:K], cols), live[:K])
        got = jitcheck.site("spmd.slicer").jit(jax.shard_map(
            body, mesh=mesh, in_specs=(PS(axis), PS(axis)),
            out_specs=(PS(axis), PS(axis)), check_vma=False))
        _SLICER_CACHE[key] = got
    return got


def _note_gather(counts_np, live_np, cols_np) -> Dict[str, int]:
    """Record the device->host gather footprint just fetched."""
    live_np = np.asarray(live_np)
    GATHER_STATS["rows"] = int(np.asarray(counts_np).sum())
    GATHER_STATS["capacity"] = int(live_np.shape[0])
    GATHER_STATS["bytes"] = int(sum(
        np.asarray(x).nbytes
        for x in jax.tree.leaves(cols_np))) + live_np.nbytes
    return GATHER_STATS


def _probe_marks(probe_box, direct_np, n_dev: int) -> Dict[str, str]:
    """{operator label: "direct" | "search" | "direct k/n"} for the K=1
    joins of one run: `direct_np` holds, per join traced with a choice,
    how many of the `n_dev` devices took the direct-address probe."""
    counts = iter(np.asarray(direct_np).tolist()
                  if direct_np is not None else ())
    marks = {}
    for label, chosen in probe_box:
        k = next(counts) if chosen else 0
        marks[label] = "direct" if k == n_dev else \
            "search" if k == 0 else f"direct {k}/{n_dev}"
    return marks


def probe_counts(probes: Dict[str, str]) -> Dict[str, int]:
    """The counter's two numbers: K=1 joins run, and those of them in
    which every device probed by direct address."""
    return {"join_probes": len(probes),
            "join_probes_direct": sum(m == "direct"
                                      for m in probes.values())}


def _width_marks(box, counts_np, n_dev: int, side: str) -> Dict[str, dict]:
    """{operator label: {side: "compact" | "full" | "compact k/n",
    "rows": width, "live": rows, "capacity": slots}} for the operators of
    one run that were traced with a choice of width — the aggregates
    (`side` "input"; each also with `cap`, the rows a device its output is
    cut to where its input is larger, the capacity ladder's rung) or the
    join chains (`side` "chain", under their first join's label):
    `counts_np` holds, per operator, how many of the `n_dev` devices
    compacted, the live rows over all devices, and how many devices ran at
    each of its widths (`rows`: the width they ran at, or `a/b` where
    devices differ; `capacity`: the slots over all devices)."""
    counts = iter(np.asarray(counts_np).tolist()
                  if counts_np is not None else ())
    marks = {}
    for what in box:
        k, live = next(counts), next(counts)
        ran = [w for w in what["widths"] if next(counts)]
        marks[what["label"]] = {
            side: "compact" if k == n_dev else
            "full" if k == 0 else f"compact {k}/{n_dev}",
            "rows": ran[0] if len(ran) == 1 else "/".join(map(str, ran)),
            "live": live, "capacity": what["capacity"],
            **({"cap": what["cap"]} if "cap" in what else {})}
    return marks


def agg_widths(mark: dict) -> List[int]:
    """The widths the devices ran one aggregate's body at, ascending, from
    its mark's `rows`."""
    return [int(w) for w in str(mark["rows"]).split("/")]


def agg_input_counts(aggs: Dict[str, dict]) -> Dict[str, int]:
    """The counter's three numbers: aggregates run with a choice of
    width, those of them whose input every device compacted, and those
    whose body every device ran under the capacity ladder's rung."""
    return {"agg_inputs": len(aggs),
            "agg_inputs_compact": sum(a["input"] == "compact"
                                      for a in aggs.values()),
            "agg_inputs_below_cap": sum(agg_widths(a)[-1] < a["cap"]
                                        for a in aggs.values())}


def chain_counts(chains: Dict[str, dict]) -> Dict[str, int]:
    """The counter's two numbers: join chains traced with a choice of
    width, and those of them whose later joins every device ran at a
    rung."""
    return {"join_chains": len(chains),
            "join_chains_compact": sum(c["chain"] == "compact"
                                       for c in chains.values())}


def segment_counts(counted: Dict[str, int]) -> Dict[str, int]:
    """The counter's two numbers: segment bounds derived while the stage
    program was traced (one an aggregate body: both sides of a choice
    count), and the sorted-segment reductions that took them."""
    return {"segment_bounds": counted.get("bounds", 0),
            "segment_reductions": counted.get("reductions", 0)}


def _crossing_stats(cross_box, cross_np) -> Dict[str, Dict[str, dict]]:
    """{"exchanges": {label: ..}, "broadcasts": {label: ..}} of one run,
    from what the tracer knew of each boundary (`cross_box`) and the
    program's counts.  An exchange: `rows` in, `rows_moved` to another
    device and their `moved_bytes`, `rows_recv_max` on the fullest device,
    `block_rows_max` of the fullest (source, destination) block against
    its `quota` as `fill_pct` (past 100 the overflow guard tripped), and
    the `buffer_bytes` a device's all_to_all carries whatever the rows.  A
    broadcast: live `rows` in `slots` gathered to every device, and the
    gathered `buffer_bytes`.  Empty on one device."""
    out: Dict[str, Dict[str, dict]] = {}
    counts = iter(np.asarray(cross_np).tolist()
                  if cross_np is not None else ())
    for what in cross_box:
        what = dict(what)
        of_kind = out.setdefault(what.pop("kind") + "s", {})
        if "quota" in what:
            rows, moved, recv, block = (next(counts) for _ in range(4))
            what.update(
                rows=rows, rows_moved=moved, rows_recv_max=recv,
                block_rows_max=block,
                fill_pct=100.0 * block / what["quota"],
                moved_bytes=moved * what.pop("row_bytes"))
        else:
            what["rows"] = next(counts)
        of_kind[what.pop("label")] = what
    return out


def _reported(probe_box, direct_np, agg_box, agg_np, chain_box, chain_np,
              cross_box, crossed_np, wide_box, segment_box,
              n_dev: int) -> Dict[str, Any]:
    """What one run's program reported of itself, as execute_plan_spmd's
    `stats` hold it; `wide_columns` (operator label -> wide decimal
    columns in its output) only where the program held one; `segments`,
    the segment bounds derived while it was traced and the reductions
    that took them (`segments.counting`)."""
    return {"join_probes": _probe_marks(probe_box, direct_np, n_dev),
            "agg_inputs": _width_marks(agg_box, agg_np, n_dev, "input"),
            "join_chains": _width_marks(chain_box, chain_np, n_dev,
                                        "chain"),
            "segments": dict(segment_box),
            **({"wide_columns": dict(wide_box)} if wide_box else {}),
            **_crossing_stats(cross_box, crossed_np)}


# what `spmd.ingest` reports of an attempt's scan leaves: leaves in the
# plan, those `_SCAN_TABLES` served, tasks run for the others, and what
# the tasks read: record batches kept, their rows and Arrow bytes, and
# the batches that were device batches on the way (none: a scan read for
# a stage hands on the Arrow it made)
INGEST_COUNTS = ("scans", "cached", "tasks", "batches", "rows", "bytes",
                 "device_batches")
# and of `_SCAN_TABLES` after the attempt's stores: entries those stores
# evicted, the Arrow bytes it holds, and those of them past its budget
# (kept because the attempt reads them; 0 where a query fits the budget)
CACHE_STATE = ("evicted", "held_bytes", "over_budget_bytes")
# what `spmd.shard` reports of an attempt's sources: those
# `_DEVICE_SHARDS` served, those padded and put, the bytes `shard.put`
# handed to `device_put` for them (their one crossing), and `CACHE_STATE`
# of that cache, in device bytes counted from the arrays
SHARD_COUNTS = ("cached", "placed", "shard_put_bytes") + CACHE_STATE
# the two of them that say where a cache stands, not what an attempt did:
# over an execute's attempts the last reading stands, the others add up
_GAUGES = ("held_bytes", "over_budget_bytes")


def _add_attempt(total: Dict[str, int], counts: Dict[str, int]) -> None:
    for name, n in counts.items():
        total[name] = n if name in _GAUGES else total.get(name, 0) + n


def ingest_totals(stats: Dict[str, Any]) -> Dict[str, int]:
    """What an execute's scan tasks read and what the two source caches
    did for it, as query totals; none in what a program reports of
    itself."""
    ingest = stats.get("ingest")
    if not ingest:
        return {}
    shard = stats.get("shard") or dict.fromkeys(SHARD_COUNTS, 0)
    return {**{"scan_" + name: ingest[name]
               for name in ("rows", "batches", "device_batches", "cached")},
            "shards_cached": shard["cached"],
            "shard_put_bytes": shard["shard_put_bytes"],
            "source_evictions": ingest["evicted"] + shard["evicted"],
            "source_over_budget_bytes": (ingest["over_budget_bytes"]
                                         + shard["over_budget_bytes"])}


def stage_totals(stats: Dict[str, Any]) -> Dict[str, Any]:
    """execute_plan_spmd's `stats` as query totals: what the scan tasks
    read, the probe counter's two numbers, the aggregate inputs' three, the
    join chains' two, the segment bounds' two, the ladder's rung and the
    wide decimal columns, and the boundaries' counts."""
    return {**ingest_totals(stats),
            **probe_counts(stats.get("join_probes") or {}),
            **agg_input_counts(stats.get("agg_inputs") or {}),
            **chain_counts(stats.get("join_chains") or {}),
            **segment_counts(stats.get("segments") or {}),
            **wide_totals(stats),
            **crossing_totals(stats)}


def wide_totals(stats: Dict[str, Any]) -> Dict[str, int]:
    """`agg_capacity`, the largest rung of the capacity ladder an
    aggregate's output was cut to (none where no aggregate was cut), and
    `wide_decimal_columns`, the wide decimal columns the program's
    operators handed on (none where it held none)."""
    out: Dict[str, int] = {}
    aggs = stats.get("agg_inputs") or {}
    if aggs:
        out["agg_capacity"] = max(a["cap"] for a in aggs.values())
    wide = stats.get("wide_columns") or {}
    if wide:
        out["wide_decimal_columns"] = sum(wide.values())
    return out


def crossing_totals(stats: Dict[str, Any]) -> Dict[str, Any]:
    """The boundaries' counts as query totals; none where nothing crossed
    devices."""
    ex = list((stats.get("exchanges") or {}).values())
    bc = list((stats.get("broadcasts") or {}).values())
    out: Dict[str, Any] = {}
    if ex:
        out.update(
            exchange_rows=sum(e["rows"] for e in ex),
            exchange_rows_moved=sum(e["rows_moved"] for e in ex),
            exchange_buffer_bytes=sum(e["buffer_bytes"] for e in ex),
            exchange_fill_pct_max=max(e["fill_pct"] for e in ex))
    if bc:
        out.update(
            broadcast_rows=sum(b["rows"] for b in bc),
            broadcast_slots=sum(b["slots"] for b in bc),
            broadcast_buffer_bytes=sum(b["buffer_bytes"] for b in bc))
    return out


def _execute_plan_spmd_once(plan: P.PlanNode, conv_ctx, mesh: Mesh,
                            source_tables: Dict[str, Any], axis,
                            match_factor: int,
                            agg_cap_hint: Optional[int] = None,
                            join_compact: bool = True,
                            stats: Optional[Dict[str, Any]] = None):
    # one `spmd.launch` span per stage attempt, with the host-visible
    # internal phases (`spmd.ingest` scan IO, `spmd.shard` pad+transfer,
    # `spmd.compile`/`spmd.run` program execution, `spmd.gather` result
    # fetch) as child spans — stage time is decomposable in trace
    # summaries instead of one opaque block
    from auron_tpu.runtime import tracing
    with tracing.span("spmd.launch", cat="spmd"):
        # the SPMD stage is a hot path: any implicit device->host fetch
        # (the compact-gather contract routes them all through
        # host_sync) is an undeclared-transfer diagnostic when jitcheck
        # is on
        with jitcheck.transfer_guard("spmd.execute"):
            return _execute_plan_spmd_once_impl(
                plan, conv_ctx, mesh, source_tables, axis, match_factor,
                agg_cap_hint=agg_cap_hint, join_compact=join_compact,
                stats=stats)


def _execute_plan_spmd_once_impl(plan: P.PlanNode, conv_ctx, mesh: Mesh,
                                 source_tables: Dict[str, Any], axis,
                                 match_factor: int,
                                 agg_cap_hint: Optional[int] = None,
                                 join_compact: bool = True,
                                 stats: Optional[Dict[str, Any]] = None):
    import dataclasses

    import pyarrow as pa
    from auron_tpu.faults import fault_point
    from auron_tpu.ir.schema import to_arrow_schema

    # injected device fault for the whole stage program: the driver
    # (execute_plan_spmd) re-runs a bounded number of times, then
    # degrades to the serial per-partition path
    fault_point("stage.execute")

    # inputs arrive rid-canonicalized from execute_plan_spmd:
    # ConvertContext mints per-query-uuid resource ids, so byte-identical
    # plans from two conversions would never hit _PROGRAM_CACHE — every
    # execute re-traced + re-compiled the shard_map program (~seconds of
    # warm time per query).  Walk-order rid tokens make equal plans
    # cache-equal AND give the jitted program a stable input-pytree
    # structure.

    if isinstance(axis, tuple):
        axis_sizes = tuple(mesh.shape[a] for a in axis)
        n_dev = int(np.prod(axis_sizes))
    else:
        axis_sizes = None
        n_dev = mesh.shape[axis]
    exchanges = getattr(conv_ctx, "exchanges", None) or {}

    # 1.-2. peel the driver-side tail, and a root single-mode exchange
    # feeding it
    tail, shadow_sort, plan = _peel_tail(plan, exchanges)

    # fast kind-level rejection BEFORE any source materialization (the
    # session materializes C2N sources only after this passes)
    precheck_plan(plan, conv_ctx)

    # 3. materialize scan leaves (host IO through the serial engine) and
    # FFI sources, then shard row-wise over the mesh
    from auron_tpu.runtime import tracing
    source_tables = dict(source_tables)
    with tracing.span("spmd.ingest", cat="spmd") as sp:
        scan_rids, scan_tables, read = _materialize_scans(plan, conv_ctx)
        sp.set_args(**read)
    source_tables.update(scan_tables)
    if stats is not None:
        # over the attempts of one execute: a retried attempt finds the
        # first one's scans cached
        _add_attempt(stats.setdefault("ingest", {}), read)

    # shard + device_put each source ONCE per (table, mesh, axis, string
    # config): repeat executes of the same query hit device-resident
    # shards and skip all host-side pad/concat/transfer work
    sharded = NamedSharding(mesh, PS(axis))
    shard_key = (_mesh_fingerprint(mesh), axis,
                 _string_cfg_fingerprint())
    host_inputs = {}
    schemas = {}
    # rows of every source on each device: what a device is given to scan
    device_rows = np.zeros(n_dev, np.int64)
    # what this attempt reads of `_DEVICE_SHARDS`: the budget evicts none
    # of it while the attempt stores the rest
    shard_reads = {_DEVICE_SHARDS.key_of(table, shard_key)
                   for table in source_tables.values()}
    placed = dict.fromkeys(SHARD_COUNTS, 0)
    with tracing.span("spmd.shard", cat="spmd",
                      sources=len(source_tables)) as shard_span:
        for rid, table in source_tables.items():
            dealt = _rows_per_device(table.num_rows, n_dev)
            device_rows += dealt
            e = _DEVICE_SHARDS.get(table, shard_key)
            if e is None:
                with tracing.span("shard.pad", cat="spmd") as sp:
                    schema, cols, live, cap = _shard_table(table, mesh,
                                                           axis)
                    if sp.armed:
                        sp.set_args(rows=table.num_rows, cap=cap,
                                    bytes=table.nbytes,
                                    host_bytes=sum(
                                        x.nbytes
                                        for x in jax.tree.leaves(cols)
                                        if isinstance(x, np.ndarray)))
                        if n_dev > 1:
                            sp.set_args(rows_max=max(dealt),
                                        rows_min=min(dealt))
                with tracing.span("shard.put", cat="spmd") as sp:
                    handed = jax.tree.leaves((cols, live))
                    put_bytes = sum(x.nbytes for x in handed)
                    e = {"schema": schema,
                         "cols": jax.tree.map(
                             lambda x: jax.device_put(x, sharded), cols),
                         "live": jax.device_put(live, sharded)}
                    sp.set_args(bytes=put_bytes, arrays=len(handed))
                    placed["shard_put_bytes"] += put_bytes
                    if sp.armed:
                        # device_put returns before the bytes have moved:
                        # a traced run waits for them here, so the span
                        # is the transfer and not its enqueue (untraced,
                        # the wait falls to the stage program's start)
                        jax.block_until_ready((e["cols"], e["live"]))
                placed["evicted"] += _DEVICE_SHARDS.put(
                    table, e, shard_key, shard_reads)
                placed["placed"] += 1
            else:
                placed["cached"] += 1
            host_inputs[rid] = (e["cols"], e["live"])
            schemas[rid] = e["schema"]
            if n_dev > 1 and stats is not None:
                stats.setdefault("sources", {})[rid] = {
                    "rows": table.num_rows,
                    "cap": e["live"].shape[0] // n_dev,
                    "rows_max": max(dealt), "rows_min": min(dealt)}
        placed.update(held_bytes=_DEVICE_SHARDS.held_bytes(),
                      over_budget_bytes=_DEVICE_SHARDS.over_budget_bytes())
        shard_span.set_args(**placed)
        if n_dev > 1 and shard_span.armed:
            shard_span.set_args(device_rows_max=int(device_rows.max()),
                                device_rows_min=int(device_rows.min()))
    if stats is not None:
        _add_attempt(stats.setdefault("shard", {}), placed)
    # program cache: repeat executions of the SAME converted plan over the
    # same input shapes reuse the compiled shard_map program (a fresh
    # jax.jit closure per call would re-trace+re-compile every time)
    from auron_tpu.config import conf as _conf
    if agg_cap_hint is None:
        agg_cap_hint = int(_conf.get("auron.spmd.agg.capacity.hint"))
    cache_key = (
        plan, axis, n_dev, match_factor, agg_cap_hint, join_compact,
        # the bucket an aggregate's rungs are rounded up to
        int(_conf.get("auron.batch.capacity.min")),
        _mesh_fingerprint(mesh),
        # EVERY config the tracer (or kernels it calls) reads at trace
        # time must appear here: rid canonicalization makes equal plans
        # cache-equal across conversions, so a flag flip between runs
        # would otherwise reuse a program compiled under the old value
        float(_conf.get("auron.spmd.exchange.quota.margin")),
        bool(_conf.get("auron.string.ascii.case.enable")),
        bool(_conf.get("auron.case.sensitive")),
        str(_conf.get("auron.sort.f64.exactbits")),
        int(_conf.get("auron.string.device.max.width")),
        str(_conf.get("auron.string.width.buckets")),
        tuple(sorted((rid, job.child, job.partitioning)
                     for rid, job in (getattr(conv_ctx, "exchanges", None)
                                      or {}).items())),
        tuple(sorted((rid, job.child)
                     for rid, job in (getattr(conv_ctx, "broadcasts", None)
                                      or {}).items())),
        tuple(sorted((rid, schemas[rid],
                      tuple((str(x.dtype), x.shape)
                            for x in jax.tree.leaves(ci)))
                     for rid, ci in host_inputs.items())),
        shadow_sort)
    cached = _PROGRAM_CACHE.get(cache_key)

    if cached is None:
        schema_box: List[Schema] = []
        # (operator label, traced with a choice) per K=1 join, filled at
        # trace time like the schema
        probe_box: List[Tuple[str, bool]] = []
        # what the tracer knew of each boundary that crossed devices
        cross_box: List[Dict[str, Any]] = []
        # and of each aggregate traced with a choice of input
        agg_box: List[Dict[str, Any]] = []
        # and of each join chain traced with a choice of width
        chain_box: List[Dict[str, Any]] = []
        # operator label -> wide decimal columns in its output
        wide_box: Dict[str, int] = {}
        # segment bounds derived in the trace, and reductions over them
        segment_box: Dict[str, int] = {}
        labels = {id(node): label
                  for _depth, node, label in operator_labels(plan, conv_ctx)}

        def program(bindings_flat):
            bindings = {
                rid: DeviceTable(schemas[rid], cols, live)
                for rid, (cols, live) in bindings_flat.items()}
            tracer = _StageTracer(conv_ctx, bindings, axis, n_dev, labels,
                                  shadow_sort=shadow_sort,
                                  scan_rids=scan_rids,
                                  axis_sizes=axis_sizes,
                                  match_factor=match_factor,
                                  agg_cap_hint=agg_cap_hint,
                                  join_compact=join_compact)
            with segments.counting() as counted:
                out = tracer.eval_node(plan)
            if not schema_box:
                schema_box.append(out.schema)
                segment_box.update(bounds=counted.bounds,
                                   reductions=counted.reductions)
                probe_box.extend((label, flag is not None)
                                 for label, flag in tracer.probes)
                cross_box.extend(what for what, _n in tracer.crossings)
                agg_box.extend(what for what, _n in tracer.agg_inputs)
                chain_box.extend(what for what, _n in tracer.join_chains)
                wide_box.update(tracer.wide_columns)
            with jax.named_scope("epilogue"):
                guards = jnp.stack(tracer.guards) if tracer.guards else \
                    jnp.zeros(0, bool)
                retry_guards = jnp.stack(tracer.retry_guards) \
                    if tracer.retry_guards else jnp.zeros(0, bool)
                shrink_guards = jnp.stack(tracer.shrink_guards) \
                    if tracer.shrink_guards else jnp.zeros(0, bool)
                join_guards = jnp.stack(tracer.join_guards) \
                    if tracer.join_guards else jnp.zeros(0, bool)
                # devices on the direct side, per join traced with a
                # choice; a program without one has no such output and
                # is the program it always was
                direct = [flag for _label, flag in tracer.probes
                          if flag is not None]
                probe_direct = jnp.stack(direct) if direct else None
                # the boundaries' counts; none on one device, likewise
                crossed = jnp.concatenate(
                    [n for _what, n in tracer.crossings]) \
                    if tracer.crossings else None
                # per aggregate traced with a choice, the devices that
                # compacted its input, its live rows and the devices at
                # each width; likewise
                agg_compact = jnp.concatenate(
                    [n for _what, n in tracer.agg_inputs]) \
                    if tracer.agg_inputs else None
                # per join chain traced with a choice, likewise
                chain_counts = jnp.concatenate(
                    [n for _what, n in tracer.join_chains]) \
                    if tracer.join_chains else None
                cols, live = out.cols, out.live
                count = jnp.sum(live.astype(jnp.int32))[None]
                # compact live rows to the shard front so the host
                # can fetch ONLY a bucket_capacity(count) slice
                # instead of the full padded capacity (VERDICT r4 #2:
                # "gather only final aggregated rows")
                perm = _live_first_perm(live)
                ok = jnp.take(live, perm)
                cols = [c.gather(perm, ok) for c in cols]
                live = ok
            return (cols, live, count, guards, retry_guards,
                    shrink_guards, join_guards, probe_direct, crossed,
                    agg_compact, chain_counts)

        shard = jitcheck.site("spmd.stage").jit(jax.shard_map(
            program, mesh=mesh,
            in_specs=(jax.tree.map(lambda _: PS(axis), host_inputs),),
            out_specs=(PS(axis), PS(axis), PS(axis), PS(), PS(), PS(),
                       PS(), PS(), PS(), PS(), PS()),
            check_vma=False))
    else:
        (shard, schema_box, probe_box, cross_box, agg_box, chain_box,
         wide_box, segment_box) = cached

    # jax.jit is lazy: on a cache miss the first call below traces +
    # compiles the whole stage program, so the span is the compile span;
    # cache hits record a pure run span (both are children of the
    # enclosing spmd.launch)
    with tracing.span(
            "spmd.compile" if cached is None else "spmd.run",
            cat="spmd", devices=n_dev):
        (out_cols, out_live, counts, guards, retry_guards, shrink_guards,
         join_guards, probe_direct, crossed, agg_compact, chain_counts) = \
            shard(host_inputs)
    if cached is None:
        _PROGRAM_CACHE[cache_key] = (shard, schema_box, probe_box,
                                     cross_box, agg_box, chain_box,
                                     wide_box, segment_box)
    out_schema = schema_box[0]

    from auron_tpu.ops.kernel_cache import host_sync
    with tracing.span("spmd.gather", cat="spmd"), \
            jitcheck.declared_transfer("spmd.gather"):  # jitcheck: waive (THE per-stage result fetch: counts+guards first, compacted slice second)
        # phase 1: a few BYTES decide everything — per-shard live
        # counts + guard bits.  A tripped guard never pays the
        # output fetch at all, and a clean run fetches only the
        # compacted slice below.  `spmd.wait` is the host's wait for
        # the stage program (`spmd.run` was its enqueue).
        with tracing.span("spmd.wait", cat="spmd") as sp:
            (counts_np, guards_np, retry_np, shrink_np, join_np,
             direct_np, crossed_np, agg_np, chain_np) = host_sync(
                (counts, guards, retry_guards, shrink_guards,
                 join_guards, probe_direct, crossed, agg_compact,
                 chain_counts))
            reported = _reported(probe_box, direct_np, agg_box, agg_np,
                                 chain_box, chain_np, cross_box, crossed_np,
                                 wide_box, segment_box, n_dev)
            sp.set_args(**stage_totals(reported))
        if stats is not None:
            # before the guards: a tripped exchange guard's fill is what
            # says why
            stats.update(reported)
        if np.any(np.asarray(guards_np)):
            raise SpmdGuardTripped(
                "runtime guard tripped (exchange quota overflow, or "
                f"duplicate build keys past match factor {match_factor}): "
                "result discarded", retryable=False, hard=True)
        if np.any(np.asarray(join_np)):
            raise SpmdGuardTripped(
                "join output overflowed the compaction target (genuine "
                "fan-out): result discarded", join_compact=True)
        if np.any(np.asarray(shrink_np)):
            raise SpmdGuardTripped(
                f"agg group count overflowed the capacity hint "
                f"{agg_cap_hint}: result discarded", shrink=True)
        if np.any(np.asarray(retry_np)):
            raise SpmdGuardTripped(
                "duplicate-key build side at match factor 1: result "
                "discarded", retryable=True)
        # phase 2: slice each shard to the smallest capacity bucket
        # that holds its rows (one tiny cached program), then fetch
        with tracing.span("spmd.fetch", cat="spmd") as sp:
            per_cap = out_live.shape[0] // n_dev
            kmax = max(int(np.max(np.asarray(counts_np))), 1)
            K = min(bucket_capacity(kmax), per_cap)
            if K < per_cap:
                slicer = _gather_slicer(mesh, axis, K, out_cols,
                                        out_live)
                out_cols, out_live = slicer(out_cols, out_live)
            out_live_np, out_cols_np = host_sync((out_live, out_cols))
            sp.set_args(**_note_gather(counts_np, out_live_np,
                                       out_cols_np))
        # the fetched slots' live rows as an Arrow table, on the host
        with tracing.span("spmd.to_arrow", cat="spmd") as sp:
            from auron_tpu.columnar.arrow_interop import column_to_arrow
            live_np = np.asarray(out_live_np)
            total = live_np.shape[0]
            arrays = []
            for f, c in zip(out_schema, out_cols_np):
                arr = column_to_arrow(f.dtype, c, total)
                arrays.append(arr.filter(pa.array(live_np)))
            table = pa.Table.from_arrays(
                arrays, schema=to_arrow_schema(out_schema))
            if sp.armed:
                sp.set_args(rows=table.num_rows, slots=total,
                            columns=len(arrays), bytes=table.nbytes)

    # 4. replay the peeled tail through the serial engine
    if tail:
        from auron_tpu.runtime.executor import execute_plan
        from auron_tpu.runtime.resources import ResourceRegistry
        from auron_tpu.ir.schema import from_arrow_schema
        with tracing.span("spmd.tail", cat="spmd"):
            replay: P.PlanNode = P.FFIReader(
                schema=from_arrow_schema(table.schema),
                resource_id="__spmd_gathered")
            for node in reversed(tail):
                replay = dataclasses.replace(node, child=replay)
            res = ResourceRegistry()
            res.put("__spmd_gathered", table.to_batches())
            table = execute_plan(replay, resources=res).to_table()
    return table


def _walk_native(node, conv_ctx):
    """Yield every native plan node reachable from `node`, following
    exchange/broadcast boundaries into their (native) children."""
    exchanges = getattr(conv_ctx, "exchanges", None) or {}
    broadcasts = getattr(conv_ctx, "broadcasts", None) or {}
    stack = [node]
    while stack:
        n = stack.pop()
        if not isinstance(n, P.PlanNode):
            continue
        yield n
        if isinstance(n, P.IpcReader):
            job = exchanges.get(n.resource_id) or \
                broadcasts.get(n.resource_id)
            if job is not None:
                stack.append(job.child)
            continue
        if isinstance(n, P.Union):
            pushed = set()           # one walk per child, not per partition
            for i in n.inputs:       # UnionInput wrappers are not plans
                if id(i.child) not in pushed:
                    pushed.add(id(i.child))
                    stack.append(i.child)
            continue
        for c in n.children_nodes():
            stack.append(c)


_PROGRAM_CACHE: Dict[Any, Any] = {}
# canonical plan -> join match factor that last succeeded (see
# execute_plan_spmd's retry)
_MATCH_FACTOR_HINT: Dict[Any, int] = {}
# canonical plan -> effective agg capacity hint that last succeeded on
# the shrink ladder (0 = shrink off); keyed with the configured hint
_SHRINK_HINT: Dict[Any, int] = {}
# canonical plan -> True when the join compaction overflowed and the
# compaction-off retry succeeded
_JOIN_COMPACT_OFF_HINT: Dict[Any, bool] = {}
# canonical plan -> True when a HARD trip survived the whole shrink
# ladder (genuine dup-key/quota failure, not shrink-induced): repeat
# executes then skip the expensive climb and fall straight to serial
_HARD_FAIL_HINT: Dict[Any, bool] = {}

# node kinds the tracer can (conditionally) express; anything else is
# rejected by precheck_plan before source materialization
_PRECHECK_OK = frozenset({
    "ffi_reader", "ipc_reader", "parquet_scan", "orc_scan", "filter",
    "projection", "rename_columns", "coalesce_batches", "debug", "agg",
    "broadcast_join", "hash_join", "broadcast_join_build_hash_map",
    "sort_merge_join", "sort", "limit", "union", "expand", "window",
})


def iter_spmd_rejections(plan, conv_ctx):
    """Yield (node, reason) for EVERY kind-level SPMD compilability
    problem in the tree — the enumerating form behind precheck_plan,
    and the source the analysis-side lint (analysis/spmd.py) turns into
    structured diagnostics instead of log lines."""
    exchanges = getattr(conv_ctx, "exchanges", None) or {}
    for node in _walk_native(plan, conv_ctx):
        if node.kind not in _PRECHECK_OK:
            yield node, f"operator not SPMD-compilable: {node.kind}"
            continue
        if node.kind == "broadcast_join" and \
                node.join_type not in _StageTracer._JOIN_TYPES:
            yield node, f"SPMD broadcast-join type {node.join_type!r}"
        if node.kind in ("hash_join", "sort_merge_join"):
            if node.join_type not in _StageTracer._JOIN_TYPES_COLOCATED:
                yield node, f"SPMD join type {node.join_type!r}"
            # shuffled joins are per-device correct only when both sides
            # were hash-exchanged on the join keys
            elif not _smj_colocated(node, exchanges):
                yield (node,
                       "join sides are not hash-colocated on the join "
                       "keys")
        if node.kind == "agg" and node.exec_mode == "single" and \
                not _single_agg_ok(node, exchanges):
            yield (node, "single-mode agg needs an exchange (or "
                         "partial/final shape)")
        if node.kind == "window" and not _window_ok(node, exchanges):
            yield node, "window needs a colocating exchange under it"
        # (limit-over-sort rejection lives in _do_limit — trace-time only,
        # one authoritative copy)
    yield from _wide_key_rejections(plan, conv_ctx)


def _mentions_wide_decimal(root) -> bool:
    """Does any type written in the tree (a source's schema, a cast, a
    literal, an aggregate's result) name a decimal of 19-38 digits?  No
    expression of a tree that names none has such a type."""
    import dataclasses
    stack, seen = [root], set()
    while stack:
        o = stack.pop()
        if isinstance(o, DataType):
            if o.is_wide_decimal:
                return True
            stack.extend(f.dtype for f in o.children or ())
        elif isinstance(o, (tuple, list)):
            stack.extend(o)
        elif dataclasses.is_dataclass(o) and not isinstance(o, type) \
                and id(o) not in seen:
            seen.add(id(o))
            stack.extend(getattr(o, f.name) for f in dataclasses.fields(o))
    return False


def _wide_key_rejections(plan, conv_ctx):
    """(node, reason) for every place a wide decimal stands where the
    stage program needs its order or its hash: a group key, a join key, a
    sort key, a window's partition, order or argument, an exchange's
    key.  The program holds a wide decimal as a value (two words a row:
    filtered, projected, summed, averaged, compared, moved), never as a
    key; the serial engine's host path takes such a plan."""
    from auron_tpu.analysis.schema_infer import SchemaContext
    from auron_tpu.exprs.typing import infer_type
    exchanges = getattr(conv_ctx, "exchanges", None) or {}
    broadcasts = getattr(conv_ctx, "broadcasts", None) or {}
    jobs = [j for j in list(exchanges.values()) + list(broadcasts.values())
            if isinstance(j.child, P.PlanNode)]
    if not any(_mentions_wide_decimal(x) for x in [plan] + jobs):
        return

    def wide(exprs, schema):
        for x in exprs:
            try:
                dt = infer_type(x, schema)
            except Exception:   # untypable: the tracer says why
                continue
            if dt.is_wide_decimal:
                return dt
        return None

    # (a tree, the expressions the exchange above it partitions by)
    roots = [(plan, ())] + [
        (j.child, getattr(getattr(j, "partitioning", None), "expressions",
                          None) or ()) for j in jobs]
    for root, exchange_keys in roots:
        sc = SchemaContext(root)
        if exchange_keys and sc.schema_of(root) is not None:
            dt = wide(exchange_keys, sc.schema_of(root))
            if dt is not None:
                yield root, f"a wide decimal ({dt!r}) as exchange key"
        for node, _path in sc.nodes():
            kids = [sc.schema_of(c) for c in P.plan_children(node)]
            if not kids or any(k is None for k in kids):
                continue
            if node.kind == "agg":
                uses = [("group key", node.grouping, kids[0])]
            elif node.kind in ("broadcast_join", "hash_join",
                               "sort_merge_join"):
                uses = [("join key", node.on.left_keys, kids[0]),
                        ("join key", node.on.right_keys, kids[1])]
            elif node.kind == "sort":
                uses = [("sort key", [x.child for x in node.sort_exprs],
                         kids[0])]
            elif node.kind == "window":
                args = [a for wf in node.window_funcs
                        for a in tuple(wf.args) +
                        (wf.agg.children if wf.agg else ())]
                uses = [("window argument",
                         list(node.partition_by) +
                         [x.child for x in node.order_by] + args, kids[0])]
            else:
                continue
            for what, exprs, schema in uses:
                dt = wide(exprs, schema)
                if dt is not None:
                    yield node, f"a wide decimal ({dt!r}) as {what}"


def precheck_plan(plan, conv_ctx) -> None:
    """Cheap kind-level SPMD compilability check (no tracing, no source
    materialization) — rejects the remaining fallbacks (smj, generate,
    sinks) up front; union/expand compile since round 2,
    window/limit/top-k-sort/range since round 3."""
    for _node, reason in iter_spmd_rejections(plan, conv_ctx):
        raise SpmdUnsupported(reason)


def _materialize_scans(plan, conv_ctx):
    """Read every Parquet/Orc scan leaf as the serial engine's tasks do
    (host IO + pruning) and keep the Arrow record batches the scans made:
    no device batch lies between a file and `_shard_table`.  rids are
    deterministic walk-order indexes so the compiled program's binding
    structure is stable across conversions.  Returns (rids, tables, what
    was read and what `_SCAN_TABLES` did: `INGEST_COUNTS`, `CACHE_STATE`).

    Scan PARTITIONS read in parallel on a thread pool (round-3 fix: one
    host thread serially materializing every split was the wall at
    sf100+; the reference streams scans per-task, parquet_exec.rs:70) —
    results reassemble in partition order so sharding stays
    deterministic."""
    import pyarrow as pa

    from auron_tpu.runtime.executor import execute_plan
    from auron_tpu.runtime.task_pool import run_tasks

    rids: Dict[int, str] = {}
    nodes: Dict[str, Any] = {}
    fps: Dict[str, Optional[Tuple]] = {}
    cached: Dict[str, Any] = {}
    # what this attempt reads of `_SCAN_TABLES`, by key: every leaf's
    # entry, served or about to be stored, which the stores below do not
    # evict
    reads = set()
    jobs: List[Tuple[str, Any, int, int]] = []
    for node in _walk_native(plan, conv_ctx):
        if node.kind not in ("parquet_scan", "orc_scan"):
            continue
        if id(node) in rids:
            continue
        rid = f"scan:{len(rids)}"
        rids[id(node)] = rid
        nodes[rid] = node
        # fingerprint BEFORE reading (a rewrite during the read changes
        # the fp the next lookup computes -> stale entry never matches)
        fps[rid] = _scan_files_fp(node)
        reads.add((node, fps[rid]))
        hit = _SCAN_TABLES.get(node, fps[rid])
        if hit is not None:
            # same table OBJECT across executes -> the device shard
            # cache's id() key hits too, so a repeat execute reads no
            # files AND transfers nothing
            cached[rid] = hit
            continue
        n_parts = max(1, len(getattr(node, "file_groups", ()) or ()))
        for pid in range(n_parts):
            jobs.append((rid, node, pid, n_parts))

    def read(job):
        rid, node, pid, n_parts = job
        return rid, pid, execute_plan(node, partition_id=pid,
                                      num_partitions=n_parts, arrow=True)

    results = run_tasks(read, jobs, "auron-scan")

    counts = dict.fromkeys(INGEST_COUNTS + CACHE_STATE, 0)
    counts.update(scans=len(nodes), cached=len(cached), tasks=len(jobs))
    per_rid: Dict[str, Dict[int, Any]] = {}
    for rid, pid, res in results:
        per_rid.setdefault(rid, {})[pid] = res
        counts["device_batches"] += res.device_batches
    tables: Dict[str, Any] = dict(cached)
    for rid, node in nodes.items():
        if rid in cached:
            continue
        parts = [res for _pid, res in sorted(per_rid[rid].items())]
        batches = [b for res in parts for b in res.batches]
        # the scan's output schema (the file's columns as projected, then
        # the partition columns), which every batch of it has
        t = pa.Table.from_batches(batches, schema=parts[0].schema)
        tables[rid] = t
        counts["evicted"] += _SCAN_TABLES.put(node, fps[rid], t, reads)
        counts["batches"] += len(batches)
        counts["rows"] += t.num_rows
        counts["bytes"] += t.nbytes
    counts.update(held_bytes=_SCAN_TABLES.held_bytes(),
                  over_budget_bytes=_SCAN_TABLES.over_budget_bytes())
    return rids, tables, counts

"""Adaptive query execution: stage-boundary replanning from OBSERVED
exchange statistics, backed by one unified cost model.

The reference's headline trick is that it intercepts Spark's
stage-by-stage AQE replanning — plans are re-optimized between stages
from observed exchange sizes (PAPER.md).  Here the driver has the same
vantage point: an exchange's map side completes before its reduce side
launches (frontend/session.py materializes dependencies stage by
stage), and the map tasks' writer output — the {partition, bytes, rows}
table every Rss/ShuffleWriterExec emits — IS the real per-partition
size histogram.  Behind `auron.adaptive.enable` the session calls
`replan()` at that boundary and the not-yet-executed remainder is
re-planned three ways:

1. **broadcast-vs-shuffle join conversion** — an exchange whose TOTAL
   observed output lands under `auron.adaptive.broadcast.threshold.
   bytes` and feeds the build side of a shuffled HashJoin is converted
   to the broadcast form (BroadcastJoinBuildHashMap + BroadcastJoin
   with a shared build cache): ONE hash table built once instead of one
   per reduce partition, and the partition-indexed fetch plan is
   replaced by a single collect of the already-pushed map output.  The
   committed map side is never thrown away — conversion only changes
   how the reduce side CONSUMES it, so durable-shuffle resume semantics
   (committed manifests, stage skips) are untouched.
2. **shuffle partition coalescing** — adjacent tiny reduce partitions
   merge toward `auron.adaptive.target.partition.bytes`: fewer reduce
   tasks, fewer jit signatures (reduce programs pad to capacity, so
   coalesced shapes reuse cached programs).  Co-partitioned exchanges
   (both sides of a shuffled join) receive the SAME grouping, computed
   from their combined per-partition bytes, so key alignment survives.
3. **skew splitting** — ONE oversized reduce partition (>
   `auron.adaptive.skew.factor` x the median and >
   `auron.adaptive.skew.min.partition.bytes`) fans out across extra
   tasks, each consuming a contiguous run of the partition's pushed
   blocks, with a final order-preserving concat (the split parts are
   adjacent partition ids, so the session's partition-ordered result
   concatenation IS the original stream order).

Every rewritten plan is re-verified by the static analyzer (including
the `adaptive` contract pass in analysis/adaptive.py) before execution;
a rewrite that fails verification is DROPPED with a structured decision
diagnostic, never executed.  Decisions land on `SessionResult.
aqe_decisions`, the query history record (`/queries/<id>`), EXPLAIN
ANALYZE and the
`auron_adaptive_{broadcast,coalesce,skew_split}_total` counters.

The unified `CostModel` holds LIVE per-signature execution history
(observed exchange bytes/rows per (plan signature, exchange ordinal))
beside two recorded per-row kernel costs, and feeds three
consumers: this module's replan thresholds, the conversion-side
projection/filter adjacency choice (frontend/converters._scan — the
SystemML-style cost-chosen fusion exposure, not a greedy rewrite), and
the admission re-forecast estimate released at each stage boundary
(serving/admission.reforecast via the scheduler-registered hook).
"""

from __future__ import annotations

import logging
import math
import struct
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from auron_tpu.config import conf
from auron_tpu.ir import plan as P
from auron_tpu.runtime import lockcheck

log = logging.getLogger("auron_tpu.adaptive")

__all__ = [
    "ExchangeStats", "AqeDecision", "FetchAction", "CostModel",
    "unified_cost_model", "enabled", "replan",
    "stats_from_map_results", "stats_from_manifest",
    "merge_partition_groups", "split_skewed_partition",
    "set_reforecast_hook", "clear_reforecast_hook",
    "stage_boundary_reforecast", "stage_mem_estimate",
]


def enabled() -> bool:
    return bool(conf.get("auron.adaptive.enable"))


# ---------------------------------------------------------------------------
# observed exchange statistics
# ---------------------------------------------------------------------------

@dataclass
class ExchangeStats:
    """Real per-reduce-partition output of one exchange's map side, as
    observed from the writer result tables (or, for a durable stage
    RESUMED from committed manifests, from the manifest's per-partition
    byte ledger — rows are then unknown)."""
    rid: str
    partition_bytes: List[int]
    partition_rows: List[int]
    rows_known: bool = True
    resumed: bool = False

    @property
    def total_bytes(self) -> int:
        return sum(self.partition_bytes)

    @property
    def total_rows(self) -> int:
        return sum(self.partition_rows)

    @property
    def num_partitions(self) -> int:
        return len(self.partition_bytes)

    def median_bytes(self) -> int:
        xs = sorted(self.partition_bytes)
        return xs[len(xs) // 2] if xs else 0

    def ordinal(self) -> str:
        """Deterministic short name for diagnostics: conversion rids are
        `shuffle:<uid>:<n>` — the trailing ordinal is stable per query
        shape while the uid is not."""
        return f"x{self.rid.rsplit(':', 1)[-1]}"

    def to_dict(self) -> Dict[str, Any]:
        return {"exchange": self.ordinal(),
                "partitions": self.num_partitions,
                "bytes_out": self.total_bytes,
                "rows_out": self.total_rows if self.rows_known else None,
                "resumed": self.resumed,
                "partition_bytes": list(self.partition_bytes)}


def stats_from_map_results(rid: str, results, n_reduce: int
                           ) -> ExchangeStats:
    """Fold the map tasks' writer output tables ({partition, bytes,
    rows} per declared partition) into one per-partition histogram."""
    bts = [0] * n_reduce
    rws = [0] * n_reduce
    for res in results:
        for rb in getattr(res, "batches", ()) or ():
            for row in rb.to_pylist():
                p = int(row["partition"])
                if 0 <= p < n_reduce:
                    bts[p] += int(row["bytes"])
                    rws[p] += int(row["rows"])
    return ExchangeStats(rid=rid, partition_bytes=bts, partition_rows=rws)


def stats_from_manifest(rid: str, man: Dict[str, Any], n_reduce: int
                        ) -> ExchangeStats:
    """Per-partition bytes of a RESUMED durable stage, read from the
    side-car manifest's committed per-(map, partition) byte ledger."""
    bts = [0] * n_reduce
    for ent in (man.get("maps") or {}).values():
        for pid, info in (ent.get("parts") or {}).items():
            p = int(pid)
            if 0 <= p < n_reduce:
                bts[p] += int(info.get("bytes", 0))
    return ExchangeStats(rid=rid, partition_bytes=bts,
                         partition_rows=[0] * n_reduce,
                         rows_known=False, resumed=True)


# ---------------------------------------------------------------------------
# decisions
# ---------------------------------------------------------------------------

@dataclass
class AqeDecision:
    """One structured replan decision (the auditable diagnostic the
    observability surfaces carry)."""
    kind: str                 # broadcast | coalesce | skew_split | declined
    exchange: str             # deterministic ordinal ("x3")
    reason: str = ""
    detail: Dict[str, Any] = field(default_factory=dict)

    def to_dict(self) -> Dict[str, Any]:
        return {"kind": self.kind, "exchange": self.exchange,
                "reason": self.reason, **self.detail}


@dataclass
class FetchAction:
    """How the session registers one exchange's reduce-side resource
    after the replan: the rewritten fetch plan."""
    kind: str                           # broadcast | coalesce | skew_split
    groups: Optional[List[List[int]]] = None   # coalesce: pid groups
    split_pid: int = -1                 # skew: partition to fan out
    split_parts: int = 1                # skew: planned fan-out width


# ---------------------------------------------------------------------------
# the unified cost model
# ---------------------------------------------------------------------------

# Per-row costs `filter_adjacency_pays` weighs, in ns: the one pair of
# numbers the kernel cost model resolved to in every run before it was
# retired (its seed: a 2^22-row gather in 52.749 ms and a filter +
# compaction in 126.191 ms, a host-CPU record of round 5).  The choice is
# default off (`auron.adaptive.fuse.adjacency.enable`); a chip measurement
# would replace both.
_GATHER_NS = 52.749 * 1e6 / (1 << 22)             # 12.58
_FILTER_COMPACT_NS = 126.191 * 1e6 / (1 << 22)    # 30.09


class CostModel:
    """The cost model: a bounded per-key history of observed exchange
    volumes ((plan signature, exchange ordinal) -> recent bytes/rows),
    recorded at every stage boundary, so repeated submissions of one
    plan shape can be costed from what the SAME exchange actually
    produced last time.

    Consumers: the replan thresholds here, the conversion-side
    filter-adjacency choice (`filter_adjacency_pays`), and the
    stage-boundary admission re-forecast (`stage_mem_estimate`)."""

    #: decoded/padded in-memory expansion of wire bytes (v2 frames are
    #: raw device layout, but capacities pad to powers of two and reduce
    #: operators hold input + output + scratch concurrently)
    MEM_EXPANSION = 8.0

    def __init__(self, keep: int = 8):
        self._keep = keep
        self._lock = lockcheck.Lock("adaptive.cost")
        self._history: Dict[Tuple[str, str], deque] = {}

    def record_exchange(self, signature: str, stats: ExchangeStats
                        ) -> None:
        if not signature:
            return
        key = (signature, stats.ordinal())
        with self._lock:
            dq = self._history.get(key)
            if dq is None:
                dq = self._history[key] = deque(maxlen=self._keep)
            dq.append((stats.total_bytes, stats.total_rows))

    def seed_exchange(self, signature: str, ordinal: str,
                      total_bytes: int, total_rows: int) -> bool:
        """Prime one (plan signature, exchange) history entry from the
        durable stats store — the learned-initial-plan feed: a fresh
        process costs a repeated plan shape from what the SAME exchange
        produced last lifetime, BEFORE its first stage runs here.  Live
        observations own the key: an entry that already has history is
        left alone."""
        if not signature or total_bytes <= 0:
            return False
        key = (signature, ordinal)
        with self._lock:
            if self._history.get(key):
                return False
            dq = self._history[key] = deque(maxlen=self._keep)
            dq.append((int(total_bytes), int(total_rows)))
            return True

    def expected_exchange_bytes(self, signature: str, ordinal: str
                                ) -> Optional[int]:
        """Largest recently observed total for this (plan, exchange) —
        the pre-execution estimate a later planner pass can consult."""
        with self._lock:
            dq = self._history.get((signature, ordinal))
            return max(b for b, _ in dq) if dq else None

    def snapshot(self) -> Dict[str, Dict[str, int]]:
        with self._lock:
            return {f"{sig}:{ordn}": {"runs": len(dq),
                                      "max_bytes": max(b for b, _ in dq)}
                    for (sig, ordn), dq in self._history.items() if dq}

    # -- decisions ---------------------------------------------------------

    def broadcast_pays(self, stats: ExchangeStats) -> bool:
        """Build-side conversion: total observed wire bytes under the
        configured threshold.  The cost argument, in kernel-model
        terms: a shuffled join pays one hash-table sort/build per
        reduce partition while the broadcast form pays exactly one —
        at N partitions the shuffled form costs ~N * rows/N * argsort
        per-row = the same sort work but N program dispatches and N
        cache entries, so a SMALL build side always favors broadcast;
        the threshold guards the other edge (a broadcast table is
        resident per task, so the conversion must stay under the
        memory the reservation planned for)."""
        thr = int(conf.get("auron.adaptive.broadcast.threshold.bytes"))
        return 0 < stats.total_bytes <= thr

    def coalesce_target_bytes(self) -> int:
        return int(conf.get("auron.adaptive.target.partition.bytes"))

    def skew_bounds(self, stats: ExchangeStats) -> Tuple[int, int]:
        """(trigger_bytes, planned split width) for the LARGEST
        partition; width sizes splits toward the coalesce target."""
        factor = float(conf.get("auron.adaptive.skew.factor"))
        floor = int(conf.get("auron.adaptive.skew.min.partition.bytes"))
        trigger = max(int(factor * stats.median_bytes()), floor)
        target = max(1, self.coalesce_target_bytes())
        biggest = max(stats.partition_bytes, default=0)
        width = max(2, math.ceil(biggest / target))
        return trigger, width

    def filter_adjacency_pays(self, predicates, schema) -> bool:
        """The PR 3 follow-up, chosen by COST (SystemML's fusion-plan
        exemplar), not greedily: should conversion keep a pushed-down
        scan filter ALSO as an explicit Filter node above the scan so
        the fuser can see (and fuse) the filter/projection chain that
        pushdown otherwise hides?

        Pays when (a) every predicate can trace into a fused device
        program (else the extra node can never fuse and is pure cost)
        and (b) the re-evaluation cost stays under the materialization
        the fused chain saves: per the recorded profile, one standalone
        operator boundary costs ~one gather per row (`_GATHER_NS`) plus
        a compaction, while re-evaluating K predicates costs
        ~K * (filter_compact - gather) per row.  With the recorded
        numbers that admits one cheap predicate and declines
        conjunctions."""
        from auron_tpu.runtime.fusion import _exprs_fusable
        if _exprs_fusable(predicates, schema) is not None:
            return False
        # residual per-row predicate cost: the filter family's measured
        # cost minus its gather/compact component
        pred_ns = _FILTER_COMPACT_NS - _GATHER_NS
        saved_ns = 2.0 * _GATHER_NS    # one avoided materialization +
        #                                the compaction the chain defers
        return len(predicates) * pred_ns <= saved_ns

    def stage_mem_estimate(self, stats_list) -> int:
        """Remaining-stage memory estimate from observed exchange
        sizes: the biggest single reduce partition, decoded and padded
        (MEM_EXPANSION), is what one reduce task holds — the honest
        re-forecast for a query whose inputs turned out light."""
        biggest = 0
        for st in stats_list:
            biggest = max(biggest, max(st.partition_bytes, default=0))
        return int(biggest * self.MEM_EXPANSION)


_MODEL: Optional[CostModel] = None


def unified_cost_model() -> CostModel:
    global _MODEL
    if _MODEL is None:
        _MODEL = CostModel()
    return _MODEL


# ---------------------------------------------------------------------------
# plan rewriting
# ---------------------------------------------------------------------------

# join types where the BUILD side never emits unmatched rows — sharing
# one broadcast build table across probe partitions cannot duplicate
# output there.  Anything else (build-side outer, full) keeps the
# shuffled form.
_BCAST_SAFE_TYPES = {
    "right": {"inner", "left", "left_semi", "left_anti", "existence"},
    "left": {"inner", "right", "right_semi", "right_anti"},
}

# operators that process rows independently of their partition's
# composition: a partition split/merge through them is value-identical
_ROW_LOCAL_KINDS = frozenset({
    "projection", "filter", "coalesce_batches", "rename_columns",
})


def _walk_plan(plan: P.PlanNode) -> List[P.PlanNode]:
    return [n for n in P.walk(plan) if isinstance(n, P.PlanNode)]


def _rebuild(plan: P.PlanNode, replacements: Dict[int, P.PlanNode],
             ctx) -> P.PlanNode:
    """Rebuild `plan` bottom-up applying `replacements` (old node id ->
    new node); rebuilt ancestors inherit the original node's partition
    count in the convert context."""
    from auron_tpu.runtime.fusion import _replace_plan_children
    order = _walk_plan(plan)
    new: Dict[int, P.PlanNode] = {}
    for node in reversed(order):
        if id(node) in replacements:
            new[id(node)] = replacements[id(node)]
            continue
        rebuilt = _replace_plan_children(node, new)
        if rebuilt is not node and id(node) in ctx.n_parts:
            ctx.set_parts(rebuilt, ctx.parts(node))
        new[id(node)] = rebuilt
    return new[id(plan)]


def _collect_exprs(plan: P.PlanNode) -> List:
    """Every expression reachable from the plan's nodes (joins keys,
    predicates, projections, sort orders...)."""
    from auron_tpu.ir.expr import Expr
    from auron_tpu.ir.node import Node
    out: List = []
    stack: List[Node] = list(_walk_plan(plan))
    seen: set = set()
    while stack:
        n = stack.pop()
        if id(n) in seen:
            continue
        seen.add(id(n))
        for c in n.children_nodes():
            if isinstance(c, Expr):
                out.append(c)
            elif isinstance(c, Node) and not isinstance(c, P.PlanNode):
                stack.append(c)
    return out


def _has_row_position_exprs(plan: P.PlanNode) -> bool:
    """Row/partition-position expressions (row_num,
    monotonically_increasing_id) bake the task layout into VALUES —
    changing the partition count would change results."""
    from auron_tpu.exprs.compiler import _tree_has_row_base
    return any(_tree_has_row_base(x) for x in _collect_exprs(plan))


def _repartition_legal(plan: P.PlanNode, ctx, n: int,
                       exchange_rids: Dict[str, int]) -> Optional[str]:
    """None when changing the reduce partition count of this consumer's
    size-`n` exchanges is value-preserving; else the decline reason.

    Legal leaves: exchange readers of the co-partitioned size-n set
    (they all receive the same regrouping), single-partition exchange
    readers (only partition 0 carries data — any grouping keeps a
    partition 0), broadcast readers and FFI sources (read in full by
    every task, count-invariant).  Scans (partition == file group),
    unions (fixed input->output partition maps) and row-position
    expressions pin the layout."""
    for node in _walk_plan(plan):
        kids = P.plan_children(node)
        if node.kind == "union":
            return "union fixes its input partition mapping"
        if kids:
            continue
        if node.kind == "ipc_reader":
            n_red = exchange_rids.get(node.resource_id)
            if n_red is None or n_red in (1, n):
                continue
            return (f"exchange {node.resource_id} has {n_red} "
                    f"partitions, not {n}")
        if node.kind == "ffi_reader":
            continue
        return f"leaf {node.kind!r} pins the partition layout"
    if _has_row_position_exprs(plan):
        return "row-position expression bakes in the task layout"
    return None


def _skew_chain_legal(plan: P.PlanNode, rid: str) -> Optional[str]:
    """Skew splitting is stricter than coalescing: the split parts of
    ONE hash partition see only a SUBSET of that partition's keys, so
    every operator above the reader must be row-local (no agg, join,
    sort, window, limit — those reason over the whole partition)."""
    reader_seen = 0
    for node in _walk_plan(plan):
        if node.kind == "ipc_reader":
            if node.resource_id != rid:
                return "a second reader shares the stage"
            reader_seen += 1
            continue
        if node.kind not in _ROW_LOCAL_KINDS:
            return f"operator {node.kind!r} is not row-local"
    if reader_seen != 1:
        return "the skewed exchange is read more than once"
    if _has_row_position_exprs(plan):
        return "row-position expression bakes in the task layout"
    return None


def _find_broadcast_site(plan: P.PlanNode, rid: str
                         ) -> Optional[Tuple[P.HashJoin, P.IpcReader, str]]:
    """The (join, reader, side) where exchange `rid`'s reader is the
    DIRECT build-side child of a shuffled HashJoin with a
    conversion-safe join type, read exactly once in the plan."""
    readers = [n for n in _walk_plan(plan)
               if n.kind == "ipc_reader" and n.resource_id == rid]
    if len(readers) != 1:
        return None
    reader = readers[0]
    parents = [n for n in _walk_plan(plan)
               if any(c is reader for c in P.plan_children(n))]
    if len(parents) != 1 or not isinstance(parents[0], P.HashJoin):
        return None
    join = parents[0]
    side = join.build_side
    build_child = join.right if side == "right" else join.left
    if build_child is not reader:
        return None
    if join.join_type not in _BCAST_SAFE_TYPES.get(side, ()):
        return None
    return join, reader, side


def _convert_to_broadcast(plan: P.PlanNode, ctx, join: P.HashJoin,
                          reader: P.IpcReader, side: str,
                          rid: str) -> P.PlanNode:
    """Rewrite the shuffled-hash-join subtree to the broadcast form.
    The reader node is reused — the session re-registers its resource
    as ONE collected block list instead of partition-indexed blocks."""
    keys = join.on.right_keys if side == "right" else join.on.left_keys
    cache_id = f"aqe:{rid.rsplit(':', 1)[-1]}:{id(join) & 0xffff:x}"
    bhm = P.BroadcastJoinBuildHashMap(child=reader, keys=keys,
                                      cache_id=cache_id)
    probe = join.left if side == "right" else join.right
    bj = P.BroadcastJoin(
        left=bhm if side == "left" else join.left,
        right=bhm if side == "right" else join.right,
        on=join.on, join_type=join.join_type, broadcast_side=side,
        cached_build_hash_map_id=cache_id,
        existence_output_name=join.existence_output_name)
    ctx.set_parts(reader, 1)
    ctx.set_parts(bhm, 1)
    ctx.set_parts(bj, ctx.parts(probe))
    return _rebuild(plan, {id(join): bj}, ctx)


def _verify_rewrite(plan: P.PlanNode) -> Optional[str]:
    """Run the FULL analyzer battery (including the adaptive contract
    pass) over a rewritten plan; None when clean, else the first error
    rendered — the caller then drops the rewrite."""
    from auron_tpu.analysis import analyze
    res = analyze(plan)
    if res.ok:
        return None
    errs = [d for d in res.diagnostics if d.severity == "error"]
    return str(errs[0]) if errs else "verifier rejected the rewrite"


def coalesce_groups(combined: List[int], target: int) -> List[List[int]]:
    """Adjacent greedy grouping toward `target` bytes per group (the
    Spark AQE coalescer's shape): consecutive partitions accumulate
    until adding the next would overflow a non-empty group."""
    groups: List[List[int]] = []
    cur: List[int] = []
    size = 0
    for pid, b in enumerate(combined):
        if cur and size + b > target:
            groups.append(cur)
            cur, size = [], 0
        cur.append(pid)
        size += b
    if cur:
        groups.append(cur)
    return groups


# ---------------------------------------------------------------------------
# replan — the stage-boundary entry point
# ---------------------------------------------------------------------------

def replan(plan: P.PlanNode, ctx, stats_by_rid: Dict[str, ExchangeStats]
           ) -> Tuple[P.PlanNode, List[AqeDecision],
                      Dict[str, FetchAction]]:
    """Re-plan `plan` (the stage about to launch) from the observed
    exchange statistics of its just-completed map sides.  Returns the
    (possibly rewritten) plan, the structured decisions, and per-rid
    fetch actions the session applies when registering reduce-side
    resources.  Partition counts in the convert context are updated for
    rewritten nodes; the session refines them again if a skew split
    lands fewer parts than planned (block granularity)."""
    from auron_tpu.runtime import counters
    model = unified_cost_model()
    decisions: List[AqeDecision] = []
    actions: Dict[str, FetchAction] = {}
    exchange_sizes = {rid: st.num_partitions
                     for rid, st in stats_by_rid.items()}

    # 1) broadcast conversion — evaluated per exchange, smallest first,
    # re-verifying after each rewrite (a dropped rewrite keeps the
    # original subtree and the partitioned fetch)
    bcast_enabled = bool(conf.get("auron.adaptive.broadcast.enable"))
    for rid, st in sorted(stats_by_rid.items(),
                          key=lambda kv: kv[1].total_bytes):
        if not bcast_enabled or not model.broadcast_pays(st):
            continue
        site = _find_broadcast_site(plan, rid)
        if site is None:
            continue
        join, reader, side = site
        candidate = _convert_to_broadcast(plan, ctx, join, reader, side,
                                          rid)
        err = _verify_rewrite(candidate)
        if err is not None:
            decisions.append(AqeDecision(
                "declined", st.ordinal(),
                reason=f"broadcast rewrite failed verification: {err}"))
            log.warning("aqe: dropped broadcast rewrite of %s: %s",
                        rid, err)
            continue
        plan = candidate
        actions[rid] = FetchAction("broadcast")
        decisions.append(AqeDecision(
            "broadcast", st.ordinal(),
            reason=f"map output {st.total_bytes}B <= threshold "
                   f"{int(conf.get('auron.adaptive.broadcast.threshold.bytes'))}B",
            detail={"bytes": st.total_bytes, "side": side,
                    "join_type": join.join_type}))
        counters.bump("adaptive_broadcast")

    # the co-partitioned remainder (exchanges still fetched partitioned)
    remaining = {rid: st for rid, st in stats_by_rid.items()
                 if rid not in actions}
    sized = {rid: st for rid, st in remaining.items()
             if st.num_partitions > 1}
    if not sized:
        return plan, decisions, actions
    n = max(st.num_partitions for st in sized.values())
    coset = {rid: st for rid, st in sized.items()
             if st.num_partitions == n}

    # 2) skew splitting — one oversized partition, strictly row-local
    # consumers only (the split parts see a key SUBSET)
    if bool(conf.get("auron.adaptive.skew.enable")) and \
            len(coset) == 1:
        rid, st = next(iter(coset.items()))
        trigger, width = model.skew_bounds(st)
        biggest = max(st.partition_bytes)
        pid = st.partition_bytes.index(biggest)
        if biggest > trigger:
            reason = _skew_chain_legal(plan, rid)
            if reason is None:
                actions[rid] = FetchAction("skew_split", split_pid=pid,
                                           split_parts=width)
                decisions.append(AqeDecision(
                    "skew_split", st.ordinal(),
                    reason=f"partition {pid} holds {biggest}B > "
                           f"trigger {trigger}B",
                    detail={"partition": pid, "bytes": biggest,
                            "planned_parts": width}))
                counters.bump("adaptive_skew_split")
                return plan, decisions, actions
            decisions.append(AqeDecision(
                "declined", st.ordinal(),
                reason=f"skew split declined: {reason}",
                detail={"partition": pid, "bytes": biggest}))

    # 3) partition coalescing — same adjacent grouping for the whole
    # co-partitioned set, from their COMBINED per-partition bytes
    if not bool(conf.get("auron.adaptive.coalesce.enable")):
        return plan, decisions, actions
    legal = _repartition_legal(plan, ctx, n,
                               {rid: sz for rid, sz in
                                exchange_sizes.items()
                                if rid in remaining})
    if legal is not None:
        if coset:
            decisions.append(AqeDecision(
                "declined", next(iter(coset.values())).ordinal(),
                reason=f"coalesce declined: {legal}"))
        return plan, decisions, actions
    combined = [0] * n
    for st in coset.values():
        for p, b in enumerate(st.partition_bytes):
            combined[p] += b
    groups = coalesce_groups(combined, model.coalesce_target_bytes())
    if len(groups) >= n:
        return plan, decisions, actions
    for rid, st in coset.items():
        actions[rid] = FetchAction("coalesce", groups=groups)
        decisions.append(AqeDecision(
            "coalesce", st.ordinal(),
            reason=f"{n} partitions -> {len(groups)} toward "
                   f"{model.coalesce_target_bytes()}B",
            detail={"from_partitions": n, "to_partitions": len(groups),
                    "bytes": st.total_bytes}))
        counters.bump("adaptive_coalesce")
    return plan, decisions, actions


# ---------------------------------------------------------------------------
# reduce-side block-list transforms (applied by the session at fetch)
# ---------------------------------------------------------------------------

_V2_MAGIC_BYTES = struct.pack("<I", 0xFFFFFFFF)


def _stream_header_of(block: bytes) -> Optional[bytes]:
    """The v2 schema header prefix of a partition stream's first block,
    or None for v1 (self-contained arrow frames)."""
    if len(block) >= 9 and bytes(block[:4]) == _V2_MAGIC_BYTES:
        (ln,) = struct.unpack_from("<I", block, 5)
        if len(block) >= 9 + ln:
            return bytes(block[:9 + ln])
    return None


def merge_partition_groups(blocks: List[List[bytes]],
                           groups: List[List[int]]) -> List[List[bytes]]:
    """Coalesce: concatenate adjacent partitions' block lists.  Every
    non-empty source stream opens with its own schema header and v2
    headers may re-arm mid-stream, so plain concatenation is a valid
    chained stream."""
    out: List[List[bytes]] = []
    for group in groups:
        merged: List[bytes] = []
        for pid in group:
            if pid < len(blocks):
                merged.extend(blocks[pid])
        out.append(merged)
    return out


def split_skewed_partition(blocks: List[List[bytes]], pid: int,
                           parts: int) -> List[List[bytes]]:
    """Skew: fan partition `pid`'s blocks out over up to `parts`
    contiguous chunks balanced by bytes.  Chunks after the first would
    open with a header-less v2 frame (headers are written once per map
    stream), so the source stream's header is re-armed at each chunk
    start.  Returns the expanded per-partition lists — the split parts
    are ADJACENT, so partition-ordered concatenation preserves the
    original stream order."""
    part = blocks[pid] if pid < len(blocks) else []
    parts = max(1, min(parts, len(part)))
    if parts <= 1:
        return blocks
    # adaptive greedy: each chunk targets an equal share of the BYTES
    # still unassigned, and never starves the chunks behind it of their
    # one-block minimum — exactly `parts` chunks come out
    total_left = sum(len(b) for b in part)
    chunks: List[List[bytes]] = []
    cur: List[bytes] = []
    size = 0
    idx = 0
    for b in part:
        cur.append(b)
        size += len(b)
        idx += 1
        chunks_behind = parts - len(chunks) - 1
        blocks_behind = len(part) - idx
        if chunks_behind > 0 and (
                size >= total_left / (parts - len(chunks)) or
                blocks_behind <= chunks_behind):
            chunks.append(cur)
            total_left -= size
            cur, size = [], 0
    if cur:
        chunks.append(cur)
    header = _stream_header_of(part[0]) if part else None
    fixed: List[List[bytes]] = []
    for ch in chunks:
        if header is not None and ch and \
                _stream_header_of(ch[0]) is None:
            ch = [header] + ch
        fixed.append(ch)
    return blocks[:pid] + fixed + blocks[pid + 1:]


# ---------------------------------------------------------------------------
# stage-boundary admission re-forecast
# ---------------------------------------------------------------------------
#
# The scheduler registers a per-query hook (serving/scheduler.py) that
# routes the session's stage-boundary estimate into AdmissionController
# .reforecast — the PR 12 path heartbeats already feed — so a query
# whose exchanges turned out light RELEASES reservation mid-query and
# the admission queue drains sooner.

_REFORECAST_LOCK = lockcheck.Lock("adaptive.reforecast")
_REFORECAST_HOOKS: Dict[str, Callable[[int, float], Optional[int]]] = {}


def set_reforecast_hook(query_id: str,
                        fn: Callable[[int, float], Optional[int]]) -> None:
    with _REFORECAST_LOCK:
        _REFORECAST_HOOKS[query_id] = fn


def clear_reforecast_hook(query_id: str) -> None:
    with _REFORECAST_LOCK:
        _REFORECAST_HOOKS.pop(query_id, None)


def stage_mem_estimate(query_id: Optional[str],
                       stats_list) -> int:
    """max(live ledger peak, cost-model remaining-stage estimate) —
    never below what the query has already USED, so a shrink can only
    reflect genuine lightness."""
    live = 0
    if query_id:
        try:
            from auron_tpu.memmgr import get_manager
            ent = get_manager().query_ledger().get(query_id)
            if ent:
                live = max(int(ent.get("used", 0)),
                           int(ent.get("peak", 0)))
        except Exception:  # pragma: no cover - ledger is best-effort
            live = 0
    return max(live, unified_cost_model().stage_mem_estimate(stats_list))


def stage_boundary_reforecast(query_id: Optional[str],
                              estimate_bytes: int,
                              age_s: float) -> Optional[int]:
    """Invoke the scheduler-registered hook (if any) with the stage
    boundary's estimate; returns the new reservation when it changed."""
    if not query_id or estimate_bytes <= 0:
        return None
    with _REFORECAST_LOCK:
        fn = _REFORECAST_HOOKS.get(query_id)
    if fn is None:
        return None
    try:
        return fn(estimate_bytes, age_s)
    except Exception:  # pragma: no cover - must never fail the query
        log.warning("stage-boundary reforecast hook failed for %s",
                    query_id, exc_info=True)
        return None

"""Pipeline-fragment fusion: rewrite maximal chains of row-local
operators into single FusedFragment nodes.

The physical planner applies `fuse_plan` behind `auron.fuse.enable`
(default on) before building the operator tree: a chain like

    limit <- projection <- filter <- coalesce_batches <- scan

lowers to ONE FusedFragment whose device stages trace into a single
jitted jnp program (ops/fused.py) — a batch crosses the Python operator
boundary once per FRAGMENT instead of once per operator, intermediate
Batch materializations disappear, and the fragment keys into
ops/kernel_cache.cached_jit so repeated shapes re-trace zero times.
(That zero is now a checked contract: cached_jit funnels the
`fused.fragment` family through the jit-site registry
(runtime/jitcheck.py), and the second-run-compiles-zero test fails if
a fragment cache key goes shape-polymorphic.)
This is the operator-fusion-plans approach of SystemML (PAPERS.md
1801.00829) and Flare's pipeline compilation (1703.08219) adapted to
XLA stage programs.

Decisions are observable: every chain the rewriter DECLINES (a fusable
kind whose expressions cannot enter one device program, a row-position
expression, a debug node) is recorded as a structured analysis
Diagnostic (severity info, pass id "fusion") on the FusionReport — the
`explain why wasn't this fused` surface the acceptance gate asks for —
and `explain(plan)` renders fragment boundaries.

`unfuse_plan` restores the exact original tree (bodies keep the
original operator nodes), which is also what `auron.fuse.enable=false`
produces by never fusing at all.
"""

from __future__ import annotations

import dataclasses
import weakref
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

from auron_tpu.analysis.diagnostics import Diagnostic
from auron_tpu.analysis.fusion import FUSABLE_KINDS, body_chain
from auron_tpu.ir import plan as P
from auron_tpu.ir.expr import Expr
from auron_tpu.ir.node import Node
from auron_tpu.ir.schema import Schema

PASS_ID = "fusion"


@dataclass
class FusionReport:
    """What one fuse_plan run did: fragments created and chains declined
    (with reasons, as analysis diagnostics — not log lines)."""
    fragments: List[P.FusedFragment] = field(default_factory=list)
    declined: List[Diagnostic] = field(default_factory=list)

    @property
    def n_fragments(self) -> int:
        return len(self.fragments)

    @property
    def ops_fused(self) -> int:
        return sum(len(body_chain(f.body)[0]) for f in self.fragments)

    def render(self) -> str:
        lines = [f"{self.n_fragments} fragment(s), "
                 f"{self.ops_fused} operator(s) fused"]
        lines += [str(d) for d in self.declined]
        return "\n".join(lines)


# ---------------------------------------------------------------------------
# per-operator legality (device-capability side; the structural side
# lives in analysis/fusion.py so the verifier stays jax-free)
# ---------------------------------------------------------------------------

def _static_host_cols(schema: Schema) -> frozenset:
    """Columns whose STATIC dtype keeps them host-resident; expressions
    over them cannot enter the fused device program.  (Strings that turn
    out oversize at runtime are handled by the fragment's per-batch slow
    path, not here.)"""
    out = []
    for f in schema.fields:
        if f.dtype.host_resident:
            out.append(f.name)
    return frozenset(out)


def _exprs_fusable(exprs, schema: Schema) -> Optional[str]:
    """None when every expression can trace into the fused program;
    otherwise the decline reason."""
    from auron_tpu.exprs.compiler import (
        _tree_has_row_base, device_capable,
    )
    host = _static_host_cols(schema)
    for x in exprs:
        if x is None:
            continue
        if _tree_has_row_base(x):
            # the running row offset depends on upstream batch counts; a
            # fused filter would renumber rows mid-fragment
            return "row-position expression (row_num / " \
                   "monotonically_increasing_id)"
        if x.kind == "column" and x.name in host:
            # a bare host-column passthrough is fine for CompiledExprs
            # but a fused filter would have to gather it on host
            return f"host-resident column {x.name!r} crosses the fragment"
        if not device_capable(x, schema, host):
            return "expression is not device-capable (host island)"
    return None


def _op_fusable(node: P.PlanNode, in_schema: Optional[Schema],
                chain_so_far: List[P.PlanNode]) -> Optional[str]:
    """None when `node` may extend a fragment whose chain is
    `chain_so_far` (input-first); otherwise the decline reason."""
    if in_schema is None:
        return "input schema could not be inferred"
    k = node.kind
    if k == "projection":
        return _exprs_fusable(node.exprs, in_schema)
    if k == "filter":
        return _exprs_fusable(node.predicates, in_schema)
    if k == "expand":
        for proj in node.projections:
            r = _exprs_fusable(proj, in_schema)
            if r is not None:
                return r
        return None
    if k == "limit":
        if any(c.kind == "expand" for c in chain_so_far):
            # a limit above an expand counts rows across the fan-out
            # lanes of every batch — host-stateful in a way the fused
            # per-lane masks cannot express
            return "limit above an expand fan-out"
        return None
    if k in ("rename_columns", "coalesce_batches"):
        return None
    return f"operator {k!r} is not row-local"


# ---------------------------------------------------------------------------
# the rewrite
# ---------------------------------------------------------------------------

def _replace_plan_children(node: Node, mapping: Dict[int, Node]) -> Node:
    """Rebuild `node` with direct plan children swapped per `mapping`
    (id -> replacement), descending through wrapper nodes."""

    def sub(v):
        if isinstance(v, P.PlanNode):
            return mapping.get(id(v), v)
        if isinstance(v, tuple):
            return tuple(sub(x) for x in v)
        if isinstance(v, Node) and not isinstance(v, Expr):
            return _replace_plan_children(v, mapping)
        return v

    kw = {}
    for f in dataclasses.fields(node):
        old = getattr(node, f.name)
        new = sub(old)
        if new is not old:
            kw[f.name] = new
    return dataclasses.replace(node, **kw) if kw else node


def fuse_plan(plan: P.PlanNode,
              report: Optional[FusionReport] = None) -> P.PlanNode:
    """Rewrite `plan`, lowering maximal row-local chains (>= 2 ops) into
    FusedFragment nodes.  Idempotent: existing fragments pass through
    untouched and are never nested."""
    from auron_tpu.analysis.schema_infer import SchemaContext
    ctx = SchemaContext(plan)
    rep = report if report is not None else FusionReport()

    order = [n for n in P.walk(plan) if isinstance(n, P.PlanNode)]
    new: Dict[int, P.PlanNode] = {}
    # idempotency: bodies of existing fragments pass through verbatim —
    # their row-local operators must not seed fragments of their own
    inside_body: set = set()
    for node in order:
        if node.kind == "fused_fragment" and node.body is not None:
            for sub in P.walk(node.body):
                inside_body.add(id(sub))

    for node in reversed(order):          # children before parents
        if id(node) in inside_body:
            new[id(node)] = node
            continue
        rebuilt = _replace_plan_children(node, new)
        if node.kind == "fused_fragment":
            new[id(node)] = rebuilt
            continue
        if node.kind in FUSABLE_KINDS:
            kids = P.plan_children(node)
            child = kids[0] if len(kids) == 1 else None
            in_schema = ctx.schema_of(child) if child is not None else None
            new_child = new.get(id(child), child) if child is not None \
                else None
            if isinstance(new_child, P.FusedFragment):
                chain, _ = body_chain(new_child.body)
                reason = _op_fusable(node, in_schema, chain)
                if reason is None:
                    body = _replace_plan_children(
                        node, {id(child): new_child.body})
                    new[id(node)] = P.FusedFragment(
                        child=new_child.child, body=body,
                        schema=ctx.schema_of(node))
                    continue
                rep.declined.append(_decline(node, reason, ctx))
            elif child is not None:
                reason = _op_fusable(node, in_schema, [])
                if reason is None:
                    body = _replace_plan_children(
                        node, {id(child): P.FragmentInput(
                            schema=in_schema)})
                    new[id(node)] = P.FusedFragment(
                        child=new_child, body=body,
                        schema=ctx.schema_of(node))
                    continue
                rep.declined.append(_decline(node, reason, ctx))
        new[id(node)] = rebuilt

    # singleton fragments fuse nothing — unwrap them back to the plain
    # operator so `explain` and the goldens only show real fragments
    root = new[id(plan)]
    root = _unwrap_singletons(root)
    for n in P.walk(root):
        if isinstance(n, P.FusedFragment):
            rep.fragments.append(n)
    return root


def _decline(node: P.PlanNode, reason: str, ctx) -> Diagnostic:
    return Diagnostic(
        severity="info", pass_id=PASS_ID, path=ctx.path_of(node),
        node_kind=node.kind, message=f"fusion declined: {reason}",
        hint="the operator executes unfused; see runtime/fusion.py "
             "legality rules")


def _unwrap_singletons(plan: P.PlanNode) -> P.PlanNode:
    order = [n for n in P.walk(plan) if isinstance(n, P.PlanNode)]
    new: Dict[int, P.PlanNode] = {}
    for node in reversed(order):
        rebuilt = _replace_plan_children(node, new)
        if isinstance(rebuilt, P.FusedFragment):
            chain, err = body_chain(rebuilt.body)
            if err is None and len(chain) < 2:
                rebuilt = _splice_body(rebuilt.body, rebuilt.child) \
                    or rebuilt
        new[id(node)] = rebuilt
    return new[id(plan)]


def _splice_body(body: P.PlanNode,
                 replacement: P.PlanNode) -> Optional[P.PlanNode]:
    """Rebuild a fragment body with its FragmentInput leaf replaced by
    `replacement` (bottom-up along the chain)."""
    chain, err = body_chain(body)
    if err is not None or not chain:
        return None
    cur = replacement
    for op in chain:                      # input-first
        inputs = P.plan_children(op)
        cur = _replace_plan_children(op, {id(inputs[0]): cur})
    return cur


def unfuse_plan(plan: P.PlanNode) -> P.PlanNode:
    """Inverse rewrite: splice every fragment's body back over its child,
    restoring the exact unfused tree."""
    order = [n for n in P.walk(plan) if isinstance(n, P.PlanNode)]
    new: Dict[int, P.PlanNode] = {}
    for node in reversed(order):
        rebuilt = _replace_plan_children(node, new)
        if isinstance(rebuilt, P.FusedFragment):
            spliced = _splice_body(rebuilt.body, rebuilt.child)
            if spliced is not None:
                rebuilt = spliced
        new[id(node)] = rebuilt
    return new[id(plan)]


# ---------------------------------------------------------------------------
# cached entry point (the planner's) + explain
# ---------------------------------------------------------------------------

# fused results keyed by original-plan identity with a weakref guard
# against id reuse (same shape as analysis._VERIFIED): re-executing one
# TaskDefinition plan across partitions/retries fuses once
_FUSED: Dict[int, Tuple["weakref.ref", P.PlanNode, FusionReport]] = {}


def fuse_plan_cached(plan: P.PlanNode
                     ) -> Tuple[P.PlanNode, FusionReport]:
    hit = _FUSED.get(id(plan))
    if hit is not None and hit[0]() is plan:
        return hit[1], hit[2]
    rep = FusionReport()
    fused = fuse_plan(plan, rep)
    try:
        # default-arg capture of the dict: at interpreter shutdown the
        # module global may already be None when the weakref fires
        _FUSED[id(plan)] = (
            weakref.ref(plan, lambda _r, _i=id(plan), _m=_FUSED:
                        _m.pop(_i, None)),
            fused, rep)
    except TypeError:
        pass
    return fused, rep


def explain(plan: P.PlanNode, indent: int = 0) -> str:
    """Plan rendering with fused fragment boundaries: fragments print as
    one `FusedFragment[op <- op <- ...]` line over their real input."""
    lines: List[str] = []
    _explain(plan, indent, lines)
    return "\n".join(lines)


def _explain(node, depth: int, lines: List[str]) -> None:
    pad = "  " * depth
    if isinstance(node, P.FusedFragment):
        chain, err = body_chain(node.body)
        ops = " <- ".join(c.kind for c in reversed(chain)) \
            if err is None else f"<malformed: {err}>"
        lines.append(f"{pad}FusedFragment[{ops}]")
        _explain(node.child, depth + 1, lines)
        return
    label = type(node).__name__ if isinstance(node, Node) \
        else type(node).__name__
    lines.append(f"{pad}{label}")
    if isinstance(node, Node):
        for c in P.plan_children(node):
            _explain(c, depth + 1, lines)

"""Trace CLI: dump, validate and summarize query traces.

    python -m auron_tpu.trace run --query q01 --sf 0.002 -o /tmp/q01.json
    python -m auron_tpu.trace validate /tmp/q01.json
    python -m auron_tpu.trace summary /tmp/q01.json --top 15
    python -m auron_tpu.trace device /tmp/profile_dir --top 20

`run` executes one TPC-DS corpus query with `auron.trace.enable` on and
writes the Chrome-trace JSON (load in chrome://tracing or
ui.perfetto.dev); `validate` re-checks the schema invariants the
Perfetto importer relies on (exit 2 on any error); `summary` prints
per-span aggregates (total and self time: a span's duration less its
children's) and the critical path.  This is the command-line
face of runtime/tracing.py, wired into CI by tools/trace_check.sh.

`device` is the stage path's per-operator view: it reads the
`.xplane.pb` a `jax.profiler` trace left under the directory, takes
each device operation's self time (a while loop without its children)
and files it under the plan-operator label (`<kind>#<i>`, the
`jax.named_scope` of `_StageTracer.eval_node`; EXPLAIN ANALYZE prints
the labelled plan) and sub-scope (`build`, `probe`, `group`, ...) found
in the operation's scope path.  A program loaded from a persistent
compile cache carries the scopes of the process that compiled it: JAX
keeps debug info out of the cache key, so a program cached before the
scopes existed files under `unlabelled` until the cache is cleared.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import sys
from typing import Dict, List, Optional, Sequence, Tuple

from auron_tpu.runtime.tracing import (
    summarize_chrome_trace, validate_chrome_trace,
)


def _load(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def _cmd_validate(args: argparse.Namespace) -> int:
    doc = _load(args.file)
    errors = validate_chrome_trace(doc)
    if errors:
        for e in errors:
            print(f"trace: {e}", file=sys.stderr)
        return 2
    n = len(doc.get("traceEvents", []))
    print(f"{args.file}: valid Chrome trace ({n} events)")
    return 0


def _cmd_summary(args: argparse.Namespace) -> int:
    doc = _load(args.file)
    print(summarize_chrome_trace(doc, top=args.top))
    return 0


def _cmd_run(args: argparse.Namespace) -> int:
    import jax
    jax.config.update("jax_platforms", args.platform)

    import tempfile

    from auron_tpu.config import conf
    from auron_tpu.frontend.session import AuronSession
    from auron_tpu.it import queries
    from auron_tpu.it.datagen import generate
    from auron_tpu.it.oracle import PyArrowEngine

    data_dir = args.data_dir or tempfile.mkdtemp(prefix="auron_trace_")
    catalog = generate(data_dir, sf=args.sf)
    plan = queries.build(args.query, catalog)
    scope = {"auron.trace.enable": True}
    if args.serial:
        # serial per-partition path: exchanges/spills materialize, so
        # shuffle + task spans appear (the single-device SPMD stage
        # program has neither)
        scope["auron.spmd.singleDevice.enable"] = False
    if args.faults:
        scope["auron.faults.spec"] = args.faults
        scope["auron.task.retries"] = 2
        scope["auron.retry.backoff.base.ms"] = 1.0
        scope["auron.retry.backoff.max.ms"] = 10.0
    if args.budget:
        # tiny-budget traced run (tools/mem_check.sh): force spill
        # pressure so the mem.* event families and the memory columns
        # provably appear
        scope["auron.memory.spill.min.trigger.bytes"] = \
            args.spill_trigger
    mgr = None
    try:
        if args.budget:
            from auron_tpu.memmgr.manager import reset_manager
            mgr = reset_manager(args.budget)
        with conf.scoped(scope):
            session = AuronSession(foreign_engine=PyArrowEngine())
            res = session.execute(plan)
    finally:
        if args.budget:
            from auron_tpu.memmgr.manager import reset_manager
            stats = mgr.stats() if mgr is not None else {}
            reset_manager()
    if args.budget:
        print(f"mem: budget={args.budget} "
              f"peak={stats.get('peak_used', 0)} "
              f"spills={stats.get('num_spills', 0)} "
              f"freed={stats.get('spill_bytes_freed', 0)} "
              f"watermarks={[c['fraction'] for c in stats.get('watermarks_crossed', [])]}")
    if res.trace is None:
        print("no trace was recorded (auron.trace.enable did not take?)",
              file=sys.stderr)
        return 2
    doc = res.trace.to_chrome_trace()
    errors = validate_chrome_trace(doc)
    if errors:
        for e in errors:
            print(f"trace: {e}", file=sys.stderr)
        return 2
    with open(args.out, "w") as f:
        json.dump(doc, f)
    print(f"{args.query}: {res.table.num_rows} rows, "
          f"{len(doc['traceEvents'])} trace events -> {args.out}")
    if args.analyze:
        print(res.explain_analyze())
    print(summarize_chrome_trace(doc, top=args.top))
    return 0


# ---------------------------------------------------------------------------
# device profile -> seconds per plan operator
# ---------------------------------------------------------------------------

# one device operation: name, scope path, start_ns, duration_ns
DeviceOp = Tuple[str, str, float, float]

UNLABELLED = "unlabelled"
_OP_LABEL = re.compile(r"^[a-z_]+#[0-9]+$")
# the scopes opened beneath an operator's (parallel/stage.py,
# ops/agg/exec.py::_group_reduce_body); anything else that follows a
# label in a scope path is a jit or primitive name
_SUB_SCOPES = frozenset({"build", "probe", "exchange", "broadcast",
                         "group", "reduce", "compact"})
# the scopes beneath a boundary's own (parallel/exchange.py, stage.py's
# counts): `exchange/scatter` fills the send buffers, `exchange/all_to_all`
# moves them
_BOUNDARY_SCOPES = frozenset({"scatter", "all_to_all", "count"})
# the 128-bit decimal kernels' scope (exprs/decimal128.py): `dec128/sum`,
# `dec128/div`, `dec128/mul`, `dec128/cmp`, `dec128/cast`
_WIDE_SCOPE = "dec128"
# what jax puts between a label and a sub-scope opened inside a branch of
# a `lax.cond` (the join's choice of probe)
_COND_PARTS = re.compile(r"^(cond|branch_[0-9]+_fun)$")


def label_of(scope_path: str) -> Tuple[str, str]:
    """(operator label, sub-scope) of a scope path such as
    `jit(program)/agg#0/broadcast_join#2/probe/jit(_take)/gather`: the
    innermost operator label and the sub-scope right under it (two deep
    beneath a boundary: `exchange/scatter`; a 128-bit kernel family
    wherever it lies beneath the label: `dec128/sum`); the stage program's
    `epilogue`; else `unlabelled`."""
    parts = [p for p in scope_path.split("/") if not _COND_PARTS.match(p)]
    for i in range(len(parts) - 1, -1, -1):
        if _OP_LABEL.match(parts[i]):
            if _WIDE_SCOPE in parts[i + 1:-1]:
                # a 128-bit kernel family (exprs/decimal128.py), however
                # deep beneath the label: `agg#3/reduce/dec128/sum/...`
                at = parts.index(_WIDE_SCOPE, i + 1)
                return parts[i], f"{_WIDE_SCOPE}/{parts[at + 1]}"
            below = parts[i + 1:i + 3]
            if not below or below[0] not in _SUB_SCOPES:
                return parts[i], ""
            if below[0] in ("exchange", "broadcast") and \
                    below[-1] in _BOUNDARY_SCOPES:
                return parts[i], "/".join(below)
            return parts[i], below[0]
    if "epilogue" in parts:
        return "epilogue", ""
    return UNLABELLED, ""


def self_times(ops: Sequence[DeviceOp]) -> List[float]:
    """Self nanoseconds of each operation, in the order given: a parent
    (a while loop, a fusion's caller) without the children nested inside
    its interval."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][2], -ops[i][3]))
    self_ns = [op[3] for op in ops]
    stack: List[int] = []
    for i in order:
        _name, _scope, start, dur = ops[i]
        while stack and ops[stack[-1]][2] + ops[stack[-1]][3] <= start:
            stack.pop()
        if stack:
            top = ops[stack[-1]]
            self_ns[stack[-1]] -= min(dur, top[2] + top[3] - start)
        stack.append(i)
    return self_ns


def device_summary(ops: Sequence[DeviceOp]) -> Dict[str, object]:
    """Self time by (operator label, sub-scope) and by operation name.
    `rows`: [label, sub, seconds, share of busy, operations, longest
    operation's name, its seconds], most seconds first; `ops`: [name,
    label, sub, seconds, count] likewise; shares sum to 1."""
    self_ns = self_times(ops)
    busy = sum(self_ns)
    groups: Dict[Tuple[str, str], List] = {}
    by_name: Dict[Tuple[str, str, str], List] = {}
    for (name, scope, _start, _dur), ns in zip(ops, self_ns):
        key = label_of(scope)
        n = by_name.setdefault((name, *key), [0.0, 0])
        n[0] += ns
        n[1] += 1
        g = groups.setdefault(key, [0.0, 0])
        g[0] += ns
        g[1] += 1
    longest: Dict[Tuple[str, str], Tuple[str, float]] = {}
    for (name, label, sub), (ns, _n) in by_name.items():
        if ns > longest.get((label, sub), ("", -1.0))[1]:
            longest[(label, sub)] = (name, ns)
    rows = [[label, sub, ns / 1e9, ns / busy if busy else 0.0, n,
             longest[(label, sub)][0], longest[(label, sub)][1] / 1e9]
            for (label, sub), (ns, n) in groups.items()]
    rows.sort(key=lambda r: -r[2])
    names = [[name, label, sub, ns / 1e9, n]
             for (name, label, sub), (ns, n) in by_name.items()]
    names.sort(key=lambda r: -r[3])
    labelled = sum(r[2] for r in rows if r[0] != UNLABELLED)
    return {"busy_s": busy / 1e9, "labelled_s": labelled,
            "rows": rows, "ops": names}


_SHAPE = re.compile(r"[a-z]+[0-9]*\[[0-9,]*\]")
_OPCODE = re.compile(r" [a-z][a-z0-9\-]*\(")


def short_op_name(hlo: str) -> str:
    """`%fusion.7 = u32[4096]{..} fusion(u32[8]{..} %a, s32[4096]{..} %b),
    kind=..` as `fusion.7 u32[4096]<-u32[8],s32[4096]`: XLA's name for
    the operation with the shapes that say what it is."""
    name, eq, rest = hlo.partition(" = ")
    name = name.lstrip("%")
    call = _OPCODE.search(rest) if eq else None
    if call is None:
        return name
    shapes = _SHAPE.findall(rest[:call.start()])
    args = _SHAPE.findall(rest[call.end():].split("), ")[0])
    return f"{name} {','.join(shapes)}<-{','.join(args)}"[:120]


def _varint(buf, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _pb_fields(buf):
    """(field number, value) of every field of one serialized protobuf
    message: an int for a varint, the bytes for a length-delimited or
    fixed-width field."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, value


def read_scope_paths(xplane_path: str) -> Dict[str, Dict[str, str]]:
    """{device plane: {event name: scope path}}.  On the v5e under jax
    0.9.0 a device event's name is its HLO text and its own stats are
    times only; the HLO metadata's op_name — the `jax.named_scope` path —
    is the stat `tf_op` of the event's *metadata*, which
    `jax.profiler.ProfileData` does not hand out.  So the few fields that
    hold it are read from the file itself (tsl's xplane.proto: XSpace.planes
    = 1; XPlane.name = 2, .event_metadata = 4, .stat_metadata = 5, both
    maps; XEventMetadata.name = 2, .stats = 5; XStat.metadata_id = 1,
    .str_value = 5, .ref_value = 7; XStatMetadata.id = 1, .name = 2)."""
    with open(xplane_path, "rb") as f:
        space = memoryview(f.read())
    out: Dict[str, Dict[str, str]] = {}
    for field, plane in _pb_fields(space):
        if field != 1:
            continue
        plane_name, stat_names, events = "", {}, []
        for pf, value in _pb_fields(plane):
            if pf == 2:
                plane_name = bytes(value).decode()
            elif pf == 5:
                meta = dict(_pb_fields(dict(_pb_fields(value))[2]))
                stat_names[meta[1]] = bytes(meta.get(2, b"")).decode()
            elif pf == 4:
                events.append(dict(_pb_fields(value))[2])
        if not plane_name.startswith("/device:"):
            continue
        scopes = out.setdefault(plane_name, {})
        for meta in events:
            name, scope = "", ""
            for mf, value in _pb_fields(meta):
                if mf == 2:
                    name = bytes(value).decode()
                elif mf == 5:
                    stat = dict(_pb_fields(value))
                    if stat_names.get(stat.get(1)) != "tf_op":
                        continue
                    scope = bytes(stat[5]).decode() if 5 in stat \
                        else stat_names.get(stat.get(7), "")
            scopes[name] = scope
    return out


def read_device_ops(profile_dir: str) -> Dict[str, List[DeviceOp]]:
    """Per device plane, the events of its "XLA Ops" line (one per
    executed HLO operation), each with its scope path."""
    from jax.profiler import ProfileData
    paths = [profile_dir] if os.path.isfile(profile_dir) else sorted(
        glob.glob(os.path.join(profile_dir, "**", "*.xplane.pb"),
                  recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {profile_dir}")
    scopes = read_scope_paths(paths[-1])
    out: Dict[str, List[DeviceOp]] = {}
    for plane in ProfileData.from_file(paths[-1]).planes:
        if not plane.name.startswith("/device:"):
            continue
        of_event = scopes.get(plane.name, {})
        for line in plane.lines:
            if line.name == "XLA Ops":
                out.setdefault(plane.name, []).extend(
                    (short_op_name(e.name), of_event.get(e.name, ""),
                     float(e.start_ns), float(e.duration_ns))
                    for e in line.events)
    return out


def _cmd_device(args: argparse.Namespace) -> int:
    try:
        planes = read_device_ops(args.profile)
    except FileNotFoundError as e:
        print(f"trace: {e}", file=sys.stderr)
        return 2
    if not planes:
        print("trace: the profile holds no device plane with an "
              "'XLA Ops' line (a CPU profile has none)", file=sys.stderr)
        return 2
    busy_of = {}
    for plane, ops in sorted(planes.items()):
        doc = device_summary(ops)
        busy = busy_of[plane] = doc["busy_s"]
        print(f"{plane}: {len(ops)} operations, busy {busy:.6f} s, "
              f"{100 * doc['labelled_s'] / busy if busy else 0:.2f} % "
              f"under an operator label")
        print(f"{'label':28} {'scope':19} {'seconds':>11} {'share':>7} "
              f"{'ops':>6}  longest operation (its seconds)")

        def show(row):
            label, sub, sec, share, n, name, name_s = row
            print(f"{label[:28]:28} {sub:19} {sec:11.6f} "
                  f"{100 * share:6.2f}% {n:6d}  {name} ({name_s:.6f})")
        for row in doc["rows"][:args.top]:
            show(row)
        # what crosses devices, however little of the time it takes
        for row in doc["rows"][args.top:]:
            if row[1].split("/")[0] in ("exchange", "broadcast"):
                show(row)
        print(f"the {args.ops} operations with most self time:")
        for name, label, sub, sec, n in doc["ops"][:args.ops]:
            where = f"{label}/{sub}" if sub else label
            print(f"  {sec:11.6f} s  x{n:<4d} {where:32} {name}")
    most = max(busy_of, key=busy_of.get)
    least = min(busy_of, key=busy_of.get)
    if len(busy_of) > 1 and busy_of[most]:
        print(f"{len(busy_of)} device planes: busiest {most} "
              f"{busy_of[most]:.6f} s, least busy {least} "
              f"{busy_of[least]:.6f} s, "
              f"{100 * (1 - busy_of[least] / busy_of[most]):.2f} % less")
    return 0


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(prog="auron_tpu.trace")
    sub = ap.add_subparsers(dest="cmd", required=True)

    run = sub.add_parser("run", help="trace one TPC-DS corpus query")
    run.add_argument("--query", default="q01")
    run.add_argument("--sf", type=float, default=0.002)
    run.add_argument("--data-dir", default=None)
    run.add_argument("-o", "--out", default="trace.json")
    run.add_argument("--platform", default="cpu")
    run.add_argument("--serial", action="store_true",
                     help="force the serial per-partition path so "
                          "shuffle/task spans materialize")
    run.add_argument("--faults", default=None,
                     help="auron.faults.spec to arm while tracing "
                          "(retry spans in the output)")
    run.add_argument("--analyze", action="store_true",
                     help="also print EXPLAIN ANALYZE for the run")
    run.add_argument("--budget", type=int, default=0,
                     help="run under a tiny memory-manager budget "
                          "(bytes) so spill pressure and mem.* events "
                          "materialize (tools/mem_check.sh)")
    run.add_argument("--spill-trigger", type=int, default=1024,
                     help="auron.memory.spill.min.trigger.bytes to use "
                          "with --budget")
    run.add_argument("--top", type=int, default=10)
    run.set_defaults(fn=_cmd_run)

    val = sub.add_parser("validate", help="schema-check a trace file")
    val.add_argument("file")
    val.set_defaults(fn=_cmd_validate)

    summ = sub.add_parser("summary", help="summarize a trace file")
    summ.add_argument("file")
    summ.add_argument("--top", type=int, default=10)
    summ.set_defaults(fn=_cmd_summary)

    dev = sub.add_parser(
        "device", help="device seconds per plan operator, from a "
                       "jax.profiler trace directory of a stage-path run")
    dev.add_argument("profile", help="the directory handed to "
                     "jax.profiler.start_trace, or one .xplane.pb")
    dev.add_argument("--top", type=int, default=30,
                     help="rows of (label, sub-scope) to print")
    dev.add_argument("--ops", type=int, default=15,
                     help="single operations to print")
    dev.set_defaults(fn=_cmd_device)

    args = ap.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())

"""Query tracing: a low-overhead span recorder + Chrome-trace export.

The reference engine mirrors per-operator metric sets to the JVM and
exposes a pprof HTTP service; what it never records is the query
LIFECYCLE — where wall time went between "the driver saw a plan" and
"the last batch crossed the FFI".  This module is that record: named
spans for plan conversion, analyzer verify, fusion rewrite, SPMD stage
compile/launch, per-(stage, partition) task execution, shuffle
push/fetch, spill write/read and retry/fallback
attempts, exportable as Chrome-trace/Perfetto JSON (load in
chrome://tracing or ui.perfetto.dev).

Design constraints (the <2% serial-bench overhead gate):

- OFF is the default and costs ONE contextvar read per span site:
  ``span(...)`` returns a shared no-op context manager when no recorder
  is armed, allocating nothing.
- ON allocates one small Span record per site; timestamps are
  ``perf_counter_ns`` deltas against the recorder's epoch (no wall-clock
  reads on the hot path; the epoch's own unix time is kept as
  ``epoch_unix_ns``, so a saved trace can be laid beside a profile) and
  the recorder is bounded (``auron.trace.max.events``; overflow
  increments ``dropped`` instead of growing without bound).
- ON also enters a ``jax.profiler.TraceAnnotation`` of the span's name:
  while a profile runs, every program span lies in its host plane, on
  its own thread's line and on the device operations' clock; with no
  profile running that is one atomic read.
- Propagation is contextvar-based, seeded by a per-query id minted in
  ``AuronSession.execute``: ``task_pool.run_tasks`` copies the ambient
  context into its worker threads, so spans recorded on pool threads
  land in the same recorder and carry the same query id as driver-side
  spans (and as `task_logging` prefixes and metric trees — one
  correlation key across all three).

The recorder also owns the process-wide QUERY HISTORY ring
(``auron.metrics.history.max``): every `AuronSession.execute` appends a
QueryRecord (id, wall time, attempts, retries, fallbacks, merged metric
totals, the trace when one was recorded) consumed by the profiling
server's `/queries` page and the Prometheus `/metrics` aggregation.
"""

from __future__ import annotations

import contextvars
import itertools
import json
import logging
import os
import threading
import time
import uuid
from dataclasses import dataclass, field
from typing import Any, Dict, Iterator, List, Optional, Tuple

from jax.profiler import TraceAnnotation

from auron_tpu.config import conf
from auron_tpu.runtime import lockcheck

log = logging.getLogger("auron_tpu.tracing")

__all__ = [
    "Span", "TraceRecorder", "QueryRecord", "QueryStats", "span", "event",
    "current_recorder", "current_query_id", "current_stats", "stats_bump",
    "start_query", "trace_scope", "active_recorder", "harvest_query",
    "stitch_traces", "timeline_mark", "timeline_durations",
    "validate_chrome_trace", "summarize_chrome_trace", "query_history",
    "record_query", "history_metric_totals", "clear_history",
]


# ---------------------------------------------------------------------------
# span recording
# ---------------------------------------------------------------------------

@dataclass
class Span:
    """One closed span; ts/dur in ns relative to the recorder epoch."""
    name: str
    cat: str
    t0_ns: int
    dur_ns: int
    tid: int
    thread: str
    args: Optional[Dict[str, Any]] = None
    # this span's id within its recorder, and the id of the span that
    # caused it: the enclosing span on the same thread, else the span
    # that submitted the task (0 = none; instants carry no id)
    id: int = 0
    parent: int = 0


def _export_args(s: Span) -> Optional[Dict[str, Any]]:
    """A span's args as exported: its own, plus `id` and `parent` —
    nesting as recorded, not guessed from intervals (which parallel scan
    tasks break)."""
    if not s.id:
        return s.args
    return {**(s.args or {}), "id": s.id, "parent": s.parent}


class TraceRecorder:
    """Thread-safe bounded span/event sink for ONE query."""

    def __init__(self, query_id: str, max_events: Optional[int] = None):
        self.query_id = query_id
        # the two clocks read back to back: t0_ns + epoch_unix_ns is a
        # span's start on the unix clock, the one a profile is anchored
        # on (its "Task Environment" plane's profile_start_time)
        self.epoch_ns = time.perf_counter_ns()
        self.epoch_unix_ns = time.time_ns()
        self.wall_start = self.epoch_unix_ns / 1e9
        self._ids = itertools.count(1)
        self.max_events = int(conf.get("auron.trace.max.events")) \
            if max_events is None else int(max_events)
        self.spans: List[Span] = []
        self.dropped = 0
        # spans removed by drain()/drain_since() so far: the absolute
        # sequence number of self.spans[0] (the incremental-export
        # cursor long-running queries page through)
        self._base_seq = 0
        self._drop_warned = False
        self._lock = lockcheck.Lock("trace.recorder")

    # hot path — called from _SpanCtx.__exit__ and event()
    def add(self, name: str, cat: str, t0_ns: int, dur_ns: int,
            args: Optional[Dict[str, Any]], span_id: int = 0,
            parent: int = 0) -> None:
        t = threading.current_thread()
        s = Span(name=name, cat=cat, t0_ns=t0_ns - self.epoch_ns,
                 dur_ns=dur_ns, tid=t.ident or 0, thread=t.name,
                 args=args or None, id=span_id, parent=parent)
        first_drop = False
        with self._lock:
            if len(self.spans) >= self.max_events:
                self.dropped += 1
                first_drop = not self._drop_warned
                self._drop_warned = True
            else:
                self.spans.append(s)
                return
        # past the cap: count the loss where it is visible — on the
        # process counter (`auron_trace_dropped_events_total`) and, via
        # `dropped`, on the exported trace's `trace_truncated` flag —
        # and say so once per query instead of dropping silently
        from auron_tpu.runtime import counters
        counters.bump("trace_dropped_events")
        if first_drop:
            log.warning(
                "trace for query %s reached auron.trace.max.events=%d; "
                "further spans are dropped (the exported trace carries "
                "trace_truncated plus the drop count)",
                self.query_id, self.max_events)

    def snapshot(self) -> List[Span]:
        with self._lock:
            return list(self.spans)

    # -- incremental export (long-running / streaming queries) ------------

    def drain(self) -> Tuple[List[Span], int]:
        """Return-and-CLEAR the completed spans recorded so far, plus
        the next absolute sequence cursor.  Periodic drains keep a
        long-running query's recorder from growing toward the event cap
        (the PR 4 follow-up: streaming queries export trace increments
        instead of buffering a query that never ends)."""
        with self._lock:
            spans = self.spans
            self.spans = []
            self._base_seq += len(spans)
            return spans, self._base_seq

    def drain_since(self, since: int) -> Tuple[List[Span], int, int]:
        """Cursor-acknowledged drain: spans below `since` were received
        by the caller (a previous response's `next_since`) and are
        FREED; everything still buffered is returned without clearing,
        so a lost response is re-served on the next poll.  Returns
        (spans, first_seq, next_since)."""
        with self._lock:
            drop = max(0, min(int(since) - self._base_seq,
                              len(self.spans)))
            if drop:
                del self.spans[:drop]
                self._base_seq += drop
            return (list(self.spans), self._base_seq,
                    self._base_seq + len(self.spans))

    # -- export -----------------------------------------------------------

    def _span_events(self, spans: List[Span],
                     pid: int) -> List[Dict[str, Any]]:
        events: List[Dict[str, Any]] = [
            {"name": "process_name", "ph": "M", "pid": pid, "tid": 0,
             "args": {"name": f"auron-tpu query {self.query_id}"}},
        ]
        threads_named = set()
        for s in spans:
            if s.tid not in threads_named:
                threads_named.add(s.tid)
                events.append({"name": "thread_name", "ph": "M",
                               "pid": pid, "tid": s.tid,
                               "args": {"name": s.thread}})
            ev: Dict[str, Any] = {
                "name": s.name, "cat": s.cat,
                "ph": "X" if s.dur_ns >= 0 else "i",
                "ts": s.t0_ns / 1000.0, "pid": pid, "tid": s.tid,
            }
            if s.dur_ns >= 0:
                ev["dur"] = s.dur_ns / 1000.0
            else:
                ev["s"] = "t"   # instant scope: thread
            args = _export_args(s)
            if args:
                ev["args"] = args
            events.append(ev)
        return events

    def _other_data(self) -> Dict[str, Any]:
        return {"query_id": self.query_id,
                "dropped_events": self.dropped,
                "trace_truncated": self.dropped > 0,
                "wall_start": self.wall_start,
                "epoch_unix_ns": self.epoch_unix_ns}

    def to_chrome_trace(self) -> Dict[str, Any]:
        """Chrome trace-event JSON (the `traceEvents` array form): spans
        as complete ("X") events, instants as "i", thread names as "M"
        metadata.  Valid for chrome://tracing and Perfetto."""
        return {"traceEvents": self._span_events(self.snapshot(),
                                                 os.getpid()),
                "displayTimeUnit": "ms",
                "otherData": self._other_data()}

    def export_spans(self, spans: List[Span],
                     next_since: Optional[int] = None) -> Dict[str, Any]:
        """Chrome-trace document over an explicit span batch (the
        drain()/drain_since() incremental-export form): flagged partial,
        carrying the cursor the next poll should pass as `since`."""
        doc = {"traceEvents": self._span_events(spans, os.getpid()),
               "displayTimeUnit": "ms",
               "otherData": {**self._other_data(), "partial": True}}
        if next_since is not None:
            doc["otherData"]["next_since"] = int(next_since)
        return doc

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome_trace(), f)
        return path


class _NoopSpan:
    """Shared do-nothing context manager: the OFF path allocates zero."""
    __slots__ = ()
    # a site whose args cost something to compute asks first
    armed = False

    def __enter__(self) -> "_NoopSpan":
        return self

    def __exit__(self, *exc) -> bool:
        return False

    def set_args(self, **args: Any) -> None:
        """No-op twin of _SpanCtx.set_args."""


_NOOP = _NoopSpan()


class _SpanCtx:
    __slots__ = ("_rec", "_name", "_cat", "_args", "_t0", "_id", "_parent",
                 "_tok", "_ann")
    armed = True

    def __init__(self, rec: TraceRecorder, name: str, cat: str,
                 args: Optional[Dict[str, Any]]):
        self._rec = rec
        self._name = name
        self._cat = cat
        self._args = args

    def __enter__(self) -> "_SpanCtx":
        self._id = next(self._rec._ids)
        # task_pool runs each task in a copy of the submitting context,
        # so a task's first span finds its submitter's span here
        self._parent = _span_id.get()
        self._tok = _span_id.set(self._id)
        self._ann = TraceAnnotation(self._name, **(self._args or {}))
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def set_args(self, **args: Any) -> None:
        """Attach args whose values only exist once the work inside the
        span ran (fetch byte totals, row counts): merged into the
        event's `args` when the span closes."""
        merged = dict(self._args or {})
        merged.update(args)
        self._args = merged

    def __exit__(self, exc_type, exc, tb) -> bool:
        dur = time.perf_counter_ns() - self._t0
        self._ann.__exit__(exc_type, exc, tb)
        try:
            _span_id.reset(self._tok)
        except ValueError:
            # closed in another context than it was opened in (a
            # generator finalized from another thread)
            _span_id.set(self._parent)
        if exc is not None:
            args = dict(self._args or {})
            args["error"] = f"{type(exc).__name__}: {exc}"
            self._args = args
        self._rec.add(self._name, self._cat, self._t0, dur, self._args,
                      self._id, self._parent)
        return False


class QueryStats:
    """Per-query attribution counters, armed by `trace_scope` alongside
    the query id and propagated to task threads the same contextvar way.

    Before the serving tier, `AuronSession.execute` attributed attempts/
    retries/fallbacks/spills to a query by DIFFING the process-global
    counters around the run — correct with one query in flight, garbage
    with two (query A's retries landed in whichever record closed next).
    Recovery and memory sites now ALSO bump the ambient QueryStats, so
    `/queries` rows stay per-query under interleaving; the process-global
    counters keep serving `/metrics` totals unchanged."""

    __slots__ = ("_lock", "_counts")
    # `host_syncs`: blocking device->host fetches (ops/kernel_cache.py::
    # host_sync, the one sanctioned fetch) the query made, the stage
    # driver's and the serial engine's alike
    KEYS = ("attempts", "retries", "fallbacks", "mem_spills",
            "mem_spill_bytes", "host_syncs")

    def __init__(self):
        self._lock = lockcheck.Lock("trace.stats")
        self._counts = dict.fromkeys(self.KEYS, 0)

    def bump(self, key: str, delta: int = 1) -> None:
        with self._lock:
            self._counts[key] = self._counts.get(key, 0) + int(delta)

    def get(self, key: str) -> int:
        with self._lock:
            return self._counts.get(key, 0)

    def snapshot(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)


_recorder: contextvars.ContextVar[Optional[TraceRecorder]] = \
    contextvars.ContextVar("auron_trace_recorder", default=None)
_query_id: contextvars.ContextVar[Optional[str]] = \
    contextvars.ContextVar("auron_query_id", default=None)
_stats: contextvars.ContextVar[Optional[QueryStats]] = \
    contextvars.ContextVar("auron_query_stats", default=None)
# id of the innermost open span (0 = none); only armed spans touch it
_span_id: contextvars.ContextVar[int] = \
    contextvars.ContextVar("auron_span_id", default=0)

# recorders of queries currently IN FLIGHT, keyed by query id — the
# incremental trace drain (`GET /queries/<id>/trace?since=`) and the
# fleet's harvest RPC read a running query's spans through here;
# trace_scope registers on entry and unregisters on exit
_ACTIVE: Dict[str, TraceRecorder] = {}
_ACTIVE_LOCK = lockcheck.Lock("trace.active")


def _register_active(query_id: str, rec: TraceRecorder) -> None:
    with _ACTIVE_LOCK:
        _ACTIVE[query_id] = rec


def _unregister_active(query_id: str, rec: TraceRecorder) -> None:
    with _ACTIVE_LOCK:
        if _ACTIVE.get(query_id) is rec:
            del _ACTIVE[query_id]


def active_recorder(query_id: str) -> Optional[TraceRecorder]:
    """The recorder of a query still inside its trace_scope, else None
    (finished queries live in the history ring instead)."""
    with _ACTIVE_LOCK:
        return _ACTIVE.get(query_id)


def current_stats() -> Optional[QueryStats]:
    return _stats.get()


def stats_bump(key: str, delta: int = 1) -> None:
    """Attribute a recovery/memory event to the ambient query (no-op
    outside a query scope — one contextvar read, mirroring `event`)."""
    sink = _stats.get()
    if sink is not None:
        sink.bump(key, delta)


def current_recorder() -> Optional[TraceRecorder]:
    return _recorder.get()


def current_query_id() -> Optional[str]:
    """The ambient query id — the ONE correlation key shared by span
    attributes, `task_logging` prefixes and the query-history record."""
    return _query_id.get()


def span(name: str, cat: str = "runtime", **args: Any):
    """Context manager timing a named span.  With no recorder armed
    (tracing off — the default) this is one contextvar read and a shared
    no-op object; `args` land in the Chrome-trace event's `args`."""
    rec = _recorder.get()
    if rec is None:
        return _NOOP
    return _SpanCtx(rec, name, cat, args or None)


def event(name: str, cat: str = "runtime", **args: Any) -> None:
    """Record an instant event (retry attempts, fallbacks, op
    completions).  No-op when tracing is off."""
    rec = _recorder.get()
    if rec is None:
        return
    rec.add(name, cat, time.perf_counter_ns(), -1, args or None)


def new_query_id() -> str:
    return uuid.uuid4().hex[:12]


class trace_scope:
    """Arm a recorder + query id for the duration of a query.

    Used by `AuronSession.execute`: when `auron.trace.enable` is set a
    TraceRecorder is created (or an explicit one is adopted), the
    contextvars are set, and on exit they are restored.  When tracing is
    disabled the scope still mints a query id (log correlation works
    without tracing) but no recorder is armed."""

    def __init__(self, query_id: Optional[str] = None,
                 recorder: Optional[TraceRecorder] = None):
        self.query_id = query_id or new_query_id()
        if recorder is not None:
            self.recorder: Optional[TraceRecorder] = recorder
        elif conf.get("auron.trace.enable"):
            self.recorder = TraceRecorder(self.query_id)
        else:
            self.recorder = None
        # always armed (cheap): the per-query attribution sink recovery
        # and memory sites bump into (see QueryStats)
        self.stats = QueryStats()
        self._tok_rec = None
        self._tok_qid = None
        self._tok_stats = None

    def __enter__(self) -> "trace_scope":
        self._tok_qid = _query_id.set(self.query_id)
        self._tok_stats = _stats.set(self.stats)
        if self.recorder is not None:
            self._tok_rec = _recorder.set(self.recorder)
            _register_active(self.query_id, self.recorder)
        return self

    def __exit__(self, *exc) -> bool:
        if self._tok_rec is not None:
            _recorder.reset(self._tok_rec)
            _unregister_active(self.query_id, self.recorder)
        if self._tok_stats is not None:
            _stats.reset(self._tok_stats)
        if self._tok_qid is not None:
            _query_id.reset(self._tok_qid)
        return False


def start_query(query_id: Optional[str] = None) -> trace_scope:
    """Alias kept for call sites that read better as a verb."""
    return trace_scope(query_id)


# ---------------------------------------------------------------------------
# Chrome-trace validation + summary (the `python -m auron_tpu.trace` CLI)
# ---------------------------------------------------------------------------

_KNOWN_PHASES = frozenset("BEXiIMCbensTfPOND(){}")


def validate_chrome_trace(doc: Any) -> List[str]:
    """Structural validation of a Chrome-trace JSON document; returns a
    list of error strings (empty = valid).  Checks the invariants the
    Perfetto importer relies on: a traceEvents array of objects, string
    names, known phase codes, numeric non-negative ts/dur, int pid/tid,
    dict args."""
    errors: List[str] = []
    if not isinstance(doc, dict):
        return [f"top level must be an object, got {type(doc).__name__}"]
    events = doc.get("traceEvents")
    if not isinstance(events, list):
        return ["missing or non-array 'traceEvents'"]
    for i, ev in enumerate(events):
        where = f"traceEvents[{i}]"
        if not isinstance(ev, dict):
            errors.append(f"{where}: not an object")
            continue
        ph = ev.get("ph")
        if not isinstance(ph, str) or ph not in _KNOWN_PHASES:
            errors.append(f"{where}: bad phase {ph!r}")
        if not isinstance(ev.get("name"), str) or not ev.get("name"):
            errors.append(f"{where}: missing name")
        if ph != "M":
            ts = ev.get("ts")
            if not isinstance(ts, (int, float)) or ts < 0:
                errors.append(f"{where}: bad ts {ts!r}")
        if ph == "X":
            dur = ev.get("dur")
            if not isinstance(dur, (int, float)) or dur < 0:
                errors.append(f"{where}: bad dur {dur!r}")
        for key in ("pid", "tid"):
            if key in ev and not isinstance(ev[key], int):
                errors.append(f"{where}: non-int {key}")
        if "args" in ev and not isinstance(ev["args"], dict):
            errors.append(f"{where}: non-object args")
        if len(errors) >= 50:
            errors.append("... (further errors suppressed)")
            break
    return errors


def _complete_events(doc: Dict) -> List[Dict]:
    return [ev for ev in doc.get("traceEvents", [])
            if isinstance(ev, dict) and ev.get("ph") == "X"]


def _span_children(spans: List[Dict]) -> Dict[int, List[int]]:
    """Containment tree over complete events: parent = smallest
    enclosing span.  Stack-based over a (start, -dur) sort; overlapping
    non-nested spans (thread interleavings) fall back to no parent."""
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i]["ts"], -spans[i].get("dur", 0)))
    children: Dict[int, List[int]] = {i: [] for i in range(len(spans))}
    stack: List[int] = []
    for i in order:
        s, e = spans[i]["ts"], spans[i]["ts"] + spans[i].get("dur", 0)
        while stack:
            top = spans[stack[-1]]
            if top["ts"] + top.get("dur", 0) >= e and top["ts"] <= s:
                break
            stack.pop()
        if stack:
            children[stack[-1]].append(i)
        stack.append(i)
    return children


def _self_times(spans: List[Dict]) -> List[float]:
    """Each complete event's duration less its children's on its own
    thread, children by the recorded `parent` (an event without an id is
    all its own).  A child on another thread (a scan task under
    `spmd.ingest`) ran beside its parent, which was waiting: that wait is
    the parent's own."""
    own = [float(ev.get("dur", 0)) for ev in spans]
    by_id = {(ev.get("pid"), (ev.get("args") or {}).get("id")): i
             for i, ev in enumerate(spans)}
    for ev in spans:
        parent = (ev.get("args") or {}).get("parent")
        i = by_id.get((ev.get("pid"), parent)) if parent else None
        if i is not None and spans[i].get("tid") == ev.get("tid"):
            own[i] -= ev.get("dur", 0)
    return [max(0.0, t) for t in own]


def summarize_chrome_trace(doc: Dict, top: int = 10) -> str:
    """Human summary: per-name aggregates (count/total/self/max) sorted by
    total time — self being a span's duration less its children's, the
    decomposition the per-layer metrics give — plus the critical path:
    from the longest span, the chain of largest enclosed spans."""
    spans = _complete_events(doc)
    if not spans:
        return "no complete spans in trace"
    agg: Dict[str, List[float]] = {}
    for ev, own in zip(spans, _self_times(spans)):
        a = agg.setdefault(ev["name"], [0, 0.0, 0.0, 0.0])
        a[0] += 1
        a[1] += ev.get("dur", 0)
        a[2] = max(a[2], ev.get("dur", 0))
        a[3] += own
    total_span = max(spans, key=lambda e: e.get("dur", 0))
    lines = [f"{len(spans)} spans, "
             f"{len(agg)} distinct names, "
             f"longest: {total_span['name']} "
             f"{total_span.get('dur', 0) / 1000.0:.3f}ms"]
    lines.append(f"{'name':32} {'count':>6} {'total_ms':>10} "
                 f"{'self_ms':>10} {'max_ms':>10}")
    by_total = sorted(agg.items(), key=lambda kv: -kv[1][1])[:top]
    for name, (n, tot, mx, own) in by_total:
        lines.append(f"{name[:32]:32} {n:6d} {tot / 1000.0:10.3f} "
                     f"{own / 1000.0:10.3f} {mx / 1000.0:10.3f}")
    # critical path: descend from the longest span into the largest
    # enclosed span at each level
    children = _span_children(spans)
    idx = spans.index(total_span)
    lines.append("critical path:")
    depth = 0
    while True:
        ev = spans[idx]
        lines.append(f"  {'  ' * depth}{ev['name']} "
                     f"{ev.get('dur', 0) / 1000.0:.3f}ms")
        kids = children.get(idx, [])
        if not kids or depth >= 20:
            break
        idx = max(kids, key=lambda i: spans[i].get("dur", 0))
        depth += 1
    return "\n".join(lines)


# ---------------------------------------------------------------------------
# process-wide query history (the /queries page + /metrics aggregation)
# ---------------------------------------------------------------------------

@dataclass
class QueryRecord:
    """One completed query: the driver-side summary the reference's
    Spark UI tab shows per execution, plus the trace when recorded."""
    query_id: str
    wall_s: float
    # structural plan signature (serving/forecast.plan_signature) — the
    # cross-surface correlation key admission forecasts, the CostModel
    # and the durable statistics store (runtime/statshist.py) share;
    # "" when neither adaptive execution nor the stats store needed it
    signature: str = ""
    rows: int = 0
    spmd: bool = False
    attempts: int = 0
    retries: int = 0
    fallbacks: int = 0
    # times this submission was preempted (kill-and-requeue) before the
    # run this record describes; patched by the serving scheduler, 0
    # for direct session executes
    preemptions: int = 0
    error: Optional[str] = None
    started_at: float = 0.0
    metric_totals: Dict[str, int] = field(default_factory=dict)
    # memory accounting (memmgr/manager.py): largest single-operator
    # peak, and the query's spill count / freed-byte delta on the pool
    mem_peak: int = 0
    mem_spills: int = 0
    mem_spill_bytes: int = 0
    # merged per-operator metric trees ([{"tasks": n, "tree": dict}]) —
    # the structure /queries/diff pairs between two runs of one plan
    metric_trees: Optional[List[Dict[str, Any]]] = None
    # lifecycle timeline ([{"state": s, "t": wall}] in transition order:
    # submitted -> queued -> admitted -> dispatched -> running ->
    # preempted/requeued -> resumed -> terminal); serving schedulers
    # patch/record the full machine, direct executes a running/terminal
    # pair
    timeline: Optional[List[Dict[str, Any]]] = None
    # adaptive execution (runtime/adaptive.py): structured stage-
    # boundary replan decisions and the observed per-exchange size
    # histograms that drove them — the /queries/<id> audit trail
    aqe_decisions: Optional[List[Dict[str, Any]]] = None
    exchange_stats: Optional[List[Dict[str, Any]]] = None
    trace: Optional[Dict[str, Any]] = None   # chrome-trace doc, if traced

    def to_dict(self, with_trace: bool = False,
                with_trees: bool = False) -> Dict[str, Any]:
        d = {"query_id": self.query_id, "wall_s": round(self.wall_s, 4),
             "signature": self.signature,
             "rows": self.rows, "spmd": self.spmd,
             "attempts": self.attempts, "retries": self.retries,
             "fallbacks": self.fallbacks,
             "preemptions": self.preemptions, "error": self.error,
             "started_at": self.started_at, "traced": self.trace is not None,
             "mem_peak": self.mem_peak, "mem_spills": self.mem_spills,
             "mem_spill_bytes": self.mem_spill_bytes,
             "timeline": self.timeline,
             "aqe_decisions": self.aqe_decisions,
             "exchange_stats": self.exchange_stats,
             "metric_totals": dict(self.metric_totals)}
        if with_trees:
            d["metric_trees"] = self.metric_trees
        if with_trace:
            d["trace"] = self.trace
        return d


_HISTORY: List[QueryRecord] = []
_HISTORY_LOCK = lockcheck.Lock("trace.history")


def record_query(rec: QueryRecord) -> None:
    from auron_tpu.runtime import counters
    # latency histogram feed (auron_query_wall_seconds on /metrics):
    # observed here so every entry point — direct executes, the serving
    # scheduler, fleet-harvested records — lands in the same buckets
    counters.observe("query_wall_seconds", rec.wall_s)
    limit = max(1, int(conf.get("auron.metrics.history.max")))
    with _HISTORY_LOCK:
        _HISTORY.append(rec)
        if len(_HISTORY) > limit:
            del _HISTORY[:len(_HISTORY) - limit]
    # durable statistics fold (runtime/statshist.py): every terminal
    # entry point funnels through here, so the store sees session,
    # scheduler and fleet-harvested records alike.  No-op (one dict
    # read) unless auron.stats.store.dir is armed.
    from auron_tpu.runtime import statshist
    statshist.on_record(rec)


def query_history() -> List[QueryRecord]:
    with _HISTORY_LOCK:
        return list(_HISTORY)


def find_query(query_id: str) -> Optional[QueryRecord]:
    with _HISTORY_LOCK:
        for rec in reversed(_HISTORY):
            if rec.query_id == query_id:
                return rec
    return None


def history_metric_totals() -> Dict[str, int]:
    """Summed per-operator metric values across recorded queries — the
    Prometheus aggregation source (`auron_query_metric_total{key=...}`)."""
    totals: Dict[str, int] = {}
    with _HISTORY_LOCK:
        for rec in _HISTORY:
            for k, v in rec.metric_totals.items():
                totals[k] = totals.get(k, 0) + int(v)
    return totals


def clear_history() -> None:
    with _HISTORY_LOCK:
        _HISTORY.clear()


# ---------------------------------------------------------------------------
# cross-process harvest + stitching (the fleet observability plane)
# ---------------------------------------------------------------------------
#
# A fleet query executes in a WORKER process (and pushes shuffle through
# the RSS side-car), so its spans are recorded against per-process
# recorder epochs the driver cannot compare directly.  The harvest wire
# therefore ships spans with ABSOLUTE source-process wall-clock
# timestamps (µs) — recorder epoch + relative offset — and the driver
# maps them onto its own timeline with a per-process clock offset
# estimated at heartbeat RTT midpoints, clamping each lane so no span
# precedes its wire-parent (the dispatch that created the work).

def _span_abs(rec: TraceRecorder, s: Span) -> Dict[str, Any]:
    """One recorder span as a harvest dict with absolute wall-µs ts."""
    return {"name": s.name, "cat": s.cat,
            "ts_us": rec.wall_start * 1e6 + s.t0_ns / 1e3,
            "dur_us": s.dur_ns / 1e3 if s.dur_ns >= 0 else -1,
            "tid": s.tid, "thread": s.thread, "args": _export_args(s)}


def _doc_abs_spans(doc: Dict[str, Any]) -> List[Dict[str, Any]]:
    """A chrome doc's X/i events as harvest dicts (absolute wall µs),
    thread names recovered from the M metadata."""
    wall0_us = float(doc.get("otherData", {}).get("wall_start", 0.0)) * 1e6
    names: Dict[int, str] = {}
    out: List[Dict[str, Any]] = []
    for ev in doc.get("traceEvents", []):
        ph = ev.get("ph")
        if ph == "M":
            if ev.get("name") == "thread_name":
                names[ev.get("tid", 0)] = \
                    (ev.get("args") or {}).get("name", "")
            continue
        if ph not in ("X", "i"):
            continue
        out.append({"name": ev.get("name"), "cat": ev.get("cat", ""),
                    "ts_us": wall0_us + float(ev.get("ts", 0)),
                    "dur_us": float(ev["dur"]) if ph == "X" else -1,
                    "tid": ev.get("tid", 0),
                    "thread": names.get(ev.get("tid", 0), ""),
                    "args": ev.get("args")})
    return out


def harvest_query(query_id: str) -> Optional[Dict[str, Any]]:
    """The worker-side half of the fleet harvest RPC.

    For a query still in flight (active recorder): DRAIN its spans —
    repeated harvests riding heartbeats move trace data to the driver
    incrementally, so a worker killed mid-query loses only the spans
    since the last heartbeat, not the whole lane.  For a finished query
    (history ring): the residual trace plus the QueryRecord summary
    (metric trees included — the driver cannot read this process's
    metric state any other way).  None when the query is unknown."""
    rec = active_recorder(query_id)
    if rec is not None:
        spans, _ = rec.drain()
        return {"complete": False, "dropped": rec.dropped,
                "spans": [_span_abs(rec, s) for s in spans]}
    qrec = find_query(query_id)
    if qrec is None:
        return None
    out: Dict[str, Any] = {"complete": True,
                           "record": qrec.to_dict(with_trees=True)}
    if qrec.trace is not None:
        other = qrec.trace.get("otherData", {})
        out["dropped"] = int(other.get("dropped_events", 0))
        out["spans"] = _doc_abs_spans(qrec.trace)
    return out


def stitch_traces(base_doc: Dict[str, Any],
                  lanes: List[Dict[str, Any]],
                  incomplete: Iterator[str] = ()) -> Dict[str, Any]:
    """Merge harvested per-process span lanes into ONE chrome trace.

    `base_doc` is the driver recorder's export — its `wall_start` is
    the stitched timebase and its events keep their pid.  Each lane is
    ``{"label", "pid", "spans", "offset_s", "anchor_us"}``: spans carry
    absolute source-process wall-µs timestamps; `offset_s` is the
    estimated (source_wall - driver_wall) clock offset (heartbeat RTT
    midpoint); `anchor_us` is the wire-parent start in the driver
    timeline — the whole lane is shifted forward (never backward) so no
    span precedes the dispatch that caused it and the merged trace
    stays monotone under clock skew.  `incomplete` lists processes
    whose final harvest was lost (a dead worker): the stitched doc is
    flagged rather than silently partial."""
    other0 = base_doc.get("otherData", {})
    base_wall_us = float(other0.get("wall_start", 0.0)) * 1e6
    events: List[Dict[str, Any]] = list(base_doc.get("traceEvents", []))
    dropped = int(other0.get("dropped_events", 0))
    for lane in lanes:
        spans = lane.get("spans") or []
        pid = int(lane.get("pid") or 0)
        label = lane.get("label") or f"pid {pid}"
        off_us = float(lane.get("offset_s") or 0.0) * 1e6
        dropped += int(lane.get("dropped") or 0)
        events.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0, "args": {"name": label}})
        shifted = [float(s["ts_us"]) - off_us - base_wall_us
                   for s in spans]
        floor = max(0.0, float(lane.get("anchor_us") or 0.0))
        lane_shift = 0.0
        if shifted:
            lo = min(shifted)
            if lo < floor:
                lane_shift = floor - lo
        threads_named = set()
        for s, ts in zip(spans, shifted):
            tid = int(s.get("tid") or 0)
            if tid not in threads_named:
                threads_named.add(tid)
                events.append({"name": "thread_name", "ph": "M",
                               "pid": pid, "tid": tid,
                               "args": {"name": s.get("thread")
                                        or f"tid {tid}"}})
            dur = float(s.get("dur_us", -1))
            ev: Dict[str, Any] = {"name": s.get("name"),
                                  "cat": s.get("cat", ""),
                                  "ph": "X" if dur >= 0 else "i",
                                  "ts": ts + lane_shift,
                                  "pid": pid, "tid": tid}
            if dur >= 0:
                ev["dur"] = dur
            else:
                ev["s"] = "t"
            if s.get("args"):
                ev["args"] = s["args"]
            events.append(ev)
    other = dict(other0)
    other.update({"stitched": True, "dropped_events": dropped,
                  "trace_truncated": dropped > 0,
                  "incomplete": sorted(incomplete)})
    return {"traceEvents": events, "displayTimeUnit": "ms",
            "otherData": other}


# ---------------------------------------------------------------------------
# lifecycle timelines (submitted -> ... -> terminal)
# ---------------------------------------------------------------------------

def timeline_mark(timeline: List[Dict[str, Any]], state: str,
                  t: Optional[float] = None) -> List[Dict[str, Any]]:
    """Append a state transition; consecutive duplicates collapse."""
    if not timeline or timeline[-1]["state"] != state:
        timeline.append({"state": state,
                         "t": time.time() if t is None else float(t)})
    return timeline


def timeline_durations(timeline: Optional[List[Dict[str, Any]]],
                       now: Optional[float] = None) -> Dict[str, float]:
    """Seconds spent per state: each entry lasts until the next
    transition; the final entry runs to `now` unless it is terminal."""
    if not timeline:
        return {}
    terminal = {"succeeded", "failed", "cancelled", "shed"}
    out: Dict[str, float] = {}
    for ent, nxt in zip(timeline, timeline[1:]):
        d = max(0.0, float(nxt["t"]) - float(ent["t"]))
        out[ent["state"]] = out.get(ent["state"], 0.0) + d
    last = timeline[-1]
    if last["state"] not in terminal:
        end = time.time() if now is None else float(now)
        out[last["state"]] = out.get(last["state"], 0.0) + \
            max(0.0, end - float(last["t"]))
    else:
        out.setdefault(last["state"], 0.0)
    return out

"""MemManager: consumer registry + wait-or-spill arbitration + accounting.

Mirrors the decision structure of auron-memmgr/src/lib.rs:303-423
(`Operation::{Spill, Wait, Nothing}`): when a consumer grows past its fair
share and the pool is exhausted, a spillable consumer is asked to spill —
ranked by observed freed-bytes-per-wall-second from the attribution
history, falling back to largest-consumer for classes with no history
(`_pick_spill_victim`; `auron.memory.spill.victim.strategy`); tiny
consumers (< MIN_TRIGGER_SIZE) are never forced.  Single-process
synchronous version: "Wait" (multi-task backpressure) degenerates into
immediate spill of the requester.

On top of the arbitration sits the resource-observability layer (Sparkle,
arXiv:1708.05746: memory behavior, not compute, dominates Spark-class
engines on big-memory machines — so memory is the one pool that must never
be a black box):

- per-consumer and pool-wide PEAK tracking (always on: two compares under
  the lock already held for the usage update);
- WATERMARK telemetry: `auron.memory.watermark.fractions` defines budget
  fractions; the first time the pool's usage climbs past each one, a
  crossing is recorded and a `mem.pressure` trace event is emitted
  (runtime/tracing.py — one contextvar read when tracing is off).  Peaks
  are monotone, so crossings fire at most once per fraction, in
  increasing order, per manager lifetime (reset_manager re-arms);
- SPILL ATTRIBUTION: every spill the manager triggers is recorded with
  the spilling consumer, the consumer whose update requested memory, the
  decision path (arbitration / self / fallback), the bytes the consumer
  reported freed, and the spill's wall time — exported through `stats()`,
  the profiling server's `/memory` endpoint and `mem.spill` trace events;
- RESERVATIONS: `add_reservation` shrinks the effective budget (the `mem`
  fault kind injects pressure this way; a production analogue is carving
  out headroom for a co-tenant runtime);
- PER-QUERY LEDGER (overload survival): every consumer registered inside
  a query scope carries the ambient query id (runtime/tracing.py), and
  usage/peak/spill counts are ledgered per query.  With
  `auron.memory.query.budget.bytes` set, a query over its own budget has
  one of its OWN consumers spilled even while the shared pool is under
  budget, and — past `auron.memory.query.kill.grace.spills` spills that
  leave it still over budget — is KILLED through the task pool's
  cancel fast-fail path (`set_kill_hook`; the serving scheduler requeues
  the victim, a bare session fails it with QueryCancelled).  The
  `query` spill-victim strategy charges arbitration to the most-over-
  budget query instead of the globally best-rate consumer — the
  reference's per-query Wait/Spill arm.  A PRESSURE HOOK
  (`set_pressure_hook`) lets the serving scheduler watch pool usage
  cross its preemption watermark without polling.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Tuple

from auron_tpu.config import conf
from auron_tpu.runtime import lockcheck

# spill-size histogram bucket upper bounds (bytes); the last bucket is
# open-ended.  Coarse powers-of-16: spill sizes span KBs (fuzz budgets)
# to GBs (real pressure) and the histogram only needs the decade.
SPILL_HIST_BOUNDS = (1 << 12, 1 << 16, 1 << 20, 1 << 24, 1 << 28)


def min_trigger_size() -> int:
    """Consumers below this size are never forced to spill (lib.rs:36;
    configurable so tiny-budget fuzz tests can exercise spill paths)."""
    return int(conf.get("auron.memory.spill.min.trigger.bytes"))


def query_budget_bytes() -> int:
    """Per-query budget (0 = per-query enforcement off; the ledger is
    maintained regardless)."""
    return int(conf.get("auron.memory.query.budget.bytes"))


def kill_grace_spills() -> int:
    return int(conf.get("auron.memory.query.kill.grace.spills"))


# -- overload hooks ---------------------------------------------------------
#
# kill hook: invoked OUTSIDE the manager lock with (query_id, reason)
# when an over-budget query has exhausted its spill grace.  The default
# routes through the task pool's preemption path; the serving scheduler
# turns the resulting QueryCancelled into a requeue.
#
# pressure hook: (callback, fraction) — invoked OUTSIDE the manager lock
# with (total_used, effective_budget) whenever an accounting update
# leaves pool usage above fraction * effective budget.  The serving
# scheduler installs this to drive watermark preemption without polling.
#
# Hooks are PER-MANAGER registrations (MemManager.set_kill_hook /
# set_pressure_hook / reset_hooks): the fleet tier runs one manager per
# executor process, and a module-level singleton would wire every
# manager in a test process to whichever scheduler registered last.
# The module-level functions below are thin COMPATIBILITY SHIMS with
# the pre-fleet semantics — a shim-installed hook is remembered and
# re-applied across reset_manager (the serving scheduler registers at
# construction and tests reset the manager afterwards), where a
# per-manager registration dies with its manager.

_COMPAT_KILL_HOOK: Optional[Callable[[str, str], None]] = None
_COMPAT_PRESSURE_HOOK: Optional[
    Tuple[Callable[[int, int], None], float]] = None


def _default_kill_hook(query_id: str, reason: str) -> None:
    from auron_tpu.runtime import task_pool
    task_pool.preempt_query(query_id, reason)


def set_kill_hook(fn: Optional[Callable[[str, str], None]]) -> None:
    """Module-level shim: override how over-budget queries are killed
    (None restores the task-pool preemption default) on the CURRENT
    manager and every manager reset_manager installs after it."""
    global _COMPAT_KILL_HOOK
    _COMPAT_KILL_HOOK = fn
    get_manager().set_kill_hook(fn)


def set_pressure_hook(fn: Callable[[int, int], None],
                      fraction: float) -> None:
    """Module-level shim: install the watermark pressure hook on the
    current manager and every manager reset_manager installs after it."""
    global _COMPAT_PRESSURE_HOOK
    _COMPAT_PRESSURE_HOOK = (fn, float(fraction))
    get_manager().set_pressure_hook(fn, fraction)


def clear_pressure_hook(fn: Optional[Callable[[int, int], None]] = None
                        ) -> None:
    """Remove the pressure hook (only if it is `fn`, when given — a
    shut-down scheduler must not uninstall its successor's hook)."""
    global _COMPAT_PRESSURE_HOOK
    if fn is None or (_COMPAT_PRESSURE_HOOK is not None
                      and _COMPAT_PRESSURE_HOOK[0] is fn):
        _COMPAT_PRESSURE_HOOK = None
    get_manager().clear_pressure_hook(fn)


def reset_hooks() -> None:
    """The hook RESET API: drop the compat slots AND the current
    manager's registrations.  Test fixtures call this so a hook
    installed by one test can never fire inside the next."""
    global _COMPAT_KILL_HOOK, _COMPAT_PRESSURE_HOOK
    _COMPAT_KILL_HOOK = None
    _COMPAT_PRESSURE_HOOK = None
    with _GLOBAL_LOCK:
        mgr = _GLOBAL
    if mgr is not None:
        mgr.reset_hooks()


def watermark_fractions() -> List[float]:
    raw = str(conf.get("auron.memory.watermark.fractions"))
    out = []
    for part in raw.split(","):
        part = part.strip()
        if not part:
            continue
        f = float(part)
        if 0.0 < f:
            out.append(f)
    return sorted(out)


class MemConsumer:
    """Operators subclass (or compose) this; `spill()` must release device
    memory (return bytes freed)."""

    def __init__(self, name: str, spillable: bool = True):
        self.name = name
        self.spillable = spillable
        self.mem_used = 0
        self.mem_peak = 0
        self._manager: Optional["MemManager"] = None
        self._metrics = None   # MetricNode sink for mem_peak (ops/base)
        self._query_id: Optional[str] = None   # set at register time

    def bind_metrics(self, node) -> None:
        """Attach the operator's MetricNode: on unregister the manager
        flushes this consumer's peak into it (`mem_peak`), which is how
        per-operator memory columns reach EXPLAIN ANALYZE."""
        self._metrics = node

    def update_mem_used(self, new_bytes: int) -> None:
        if self._manager is not None:
            self._manager.update(self, int(new_bytes))
        else:
            self.mem_used = int(new_bytes)
            if self.mem_used > self.mem_peak:
                self.mem_peak = self.mem_used

    def spill(self) -> int:
        raise NotImplementedError


@dataclass
class SpillRecord:
    """One attributed spill: who spilled, who asked, which decision path,
    what it bought, and what it cost."""
    consumer: str          # the consumer whose spill() ran
    requested_by: str      # the consumer whose update() went over budget
    path: str              # arbitration | self | fallback
    freed_bytes: int       # the consumer's reported return value
    wall_ns: int
    total_used: int        # pool usage right after the spill
    at: float = field(default_factory=time.time)

    def to_dict(self) -> Dict[str, Any]:
        return {"consumer": self.consumer,
                "requested_by": self.requested_by, "path": self.path,
                "freed_bytes": self.freed_bytes, "wall_ns": self.wall_ns,
                "total_used": self.total_used, "at": self.at}


class MemManager:
    # bounded attribution ring: enough to see a whole spill storm, small
    # enough that accounting can stay always-on
    MAX_SPILL_RECORDS = 256
    # bounded per-query ledger: drained (used == 0) entries are evicted
    # oldest-first past this, so a long-lived serving process never
    # grows the ledger without bound
    MAX_QUERY_LEDGER = 256

    def __init__(self, budget_bytes: Optional[int] = None):
        # re-entrancy DECLARED (the PR 5 scar made it explicit): a
        # consumer's spill() re-enters update() to account what it
        # shed; the arbitration itself runs outside the lock, but the
        # nested accounting path may touch it while held
        self._lock = lockcheck.RLock("mem.manager", reentrant=True)
        self._tls = threading.local()   # re-entrancy guard (see update)
        self._consumers: List[MemConsumer] = []
        self.budget = budget_bytes if budget_bytes is not None \
            else self._default_budget()
        self.total_used = 0
        self.peak_used = 0
        self.num_spills = 0
        self.reserved = 0
        self._reservations: Dict[str, int] = {}
        # watermark state: fractions sorted ascending, next index to fire
        self._wm_fractions = watermark_fractions()
        self._wm_next = 0
        self._wm_crossings: List[Dict[str, Any]] = []
        # spill attribution: ring of records + cumulative aggregates
        self._spill_records: List[SpillRecord] = []
        self.spill_bytes_freed = 0
        self.spill_wall_ns = 0
        self._spills_by_path: Dict[str, int] = {}
        self._spill_hist = [0] * (len(SPILL_HIST_BOUNDS) + 1)
        # cumulative per-consumer-name stats, surviving unregistration
        self._by_name: Dict[str, Dict[str, int]] = {}
        # per-QUERY ledger: usage/peak/spills keyed by the query id the
        # consumer was registered under (insertion-ordered; drained
        # entries are pruned past MAX_QUERY_LEDGER)
        self._queries: Dict[str, Dict[str, int]] = {}
        self._killed_queries: set = set()   # kill hook fired once per id
        # per-MANAGER overload hooks (None kill hook = the task-pool
        # preemption default); plain attribute writes — hooks are read
        # under the accounting lock and invoked outside it
        self._kill_hook: Optional[Callable[[str, str], None]] = None
        self._pressure_hook: Optional[
            Tuple[Callable[[int, int], None], float]] = None

    # -- overload hook registration (per manager) ---------------------------

    def set_kill_hook(self,
                      fn: Optional[Callable[[str, str], None]]) -> None:
        """Override how this manager kills over-budget queries (None
        restores the task-pool preemption default)."""
        self._kill_hook = fn

    def set_pressure_hook(self, fn: Callable[[int, int], None],
                          fraction: float) -> None:
        self._pressure_hook = (fn, float(fraction))

    def clear_pressure_hook(
            self, fn: Optional[Callable[[int, int], None]] = None) -> None:
        """Remove this manager's pressure hook (only if it is `fn`,
        when given)."""
        if fn is None or (self._pressure_hook is not None
                          and self._pressure_hook[0] is fn):
            self._pressure_hook = None

    def reset_hooks(self) -> None:
        self._kill_hook = None
        self._pressure_hook = None

    @staticmethod
    def _default_budget() -> int:
        override = int(conf.get("auron.memory.budget.bytes"))
        if override:
            return override
        frac = float(conf.get("auron.memory.fraction"))
        import jax
        dev = jax.devices()[0]
        limit = (dev.memory_stats() or {}).get("bytes_limit")
        if limit:
            return int(limit * frac)
        if dev.platform != "cpu":
            # budgeting an accelerator from a guessed size either wastes
            # most of its memory or spills far too late
            raise RuntimeError(
                f"{dev.device_kind} reports no memory limit "
                f"(memory_stats() has no 'bytes_limit'); set "
                f"auron.memory.budget.bytes")
        # the CPU backend reports no memory stats: a 4GB-class budget
        return int(4 * (1 << 30) * frac)

    # -- effective budget / reservations ----------------------------------

    @property
    def effective_budget(self) -> int:
        return self.budget - self.reserved

    def add_reservation(self, label: str, nbytes: int) -> int:
        """Carve `nbytes` out of the budget under `label` (repeat labels
        accumulate).  The `mem` fault kind injects pressure through this:
        consumers see a smaller effective budget and start spilling.
        Returns the new effective budget."""
        with self._lock:
            self._reservations[label] = \
                self._reservations.get(label, 0) + int(nbytes)
            self.reserved += int(nbytes)
            return self.effective_budget

    def release_reservations(self, label: Optional[str] = None) -> None:
        with self._lock:
            if label is None:
                self._reservations.clear()
                self.reserved = 0
            else:
                self.reserved -= self._reservations.pop(label, 0)

    # -- consumer registry -------------------------------------------------

    def register_consumer(self, consumer: MemConsumer) -> MemConsumer:
        # the consumer is charged to the AMBIENT query (the task thread
        # carries the query's context — the PR 6 attribution contract);
        # read outside the lock, one contextvar access
        from auron_tpu.runtime import tracing
        qid = tracing.current_query_id()
        with self._lock:
            consumer._manager = self
            consumer._query_id = qid
            # spill() mutates operator internals, so only the thread
            # running the operator's task may invoke it (parallel
            # partition tasks each register their own consumers)
            consumer._owner_thread = threading.get_ident()
            self._consumers.append(consumer)
            ent = self._by_name.setdefault(
                consumer.name, {"registrations": 0, "peak": 0,
                                "spills": 0, "freed_bytes": 0,
                                "wall_ns": 0})
            ent["registrations"] += 1
            if qid is not None:
                self._query_ent_locked(qid)
        return consumer

    def _query_ent_locked(self, qid: str) -> Dict[str, int]:
        ent = self._queries.get(qid)
        if ent is None:
            ent = self._queries[qid] = {"used": 0, "peak": 0,
                                        "spills": 0, "kills": 0}
            if len(self._queries) > self.MAX_QUERY_LEDGER:
                for old, old_ent in list(self._queries.items()):
                    if old_ent["used"] == 0 and old != qid:
                        del self._queries[old]
                        self._killed_queries.discard(old)
                        if len(self._queries) <= self.MAX_QUERY_LEDGER:
                            break
        return ent

    def unregister_consumer(self, consumer: MemConsumer) -> None:
        with self._lock:
            if consumer in self._consumers:
                self.total_used -= consumer.mem_used
                qid = consumer._query_id
                if qid is not None and qid in self._queries:
                    self._queries[qid]["used"] -= consumer.mem_used
                consumer.mem_used = 0
                consumer._manager = None
                self._consumers.remove(consumer)
                ent = self._by_name.get(consumer.name)
                if ent is not None and consumer.mem_peak > ent["peak"]:
                    ent["peak"] = consumer.mem_peak
        node = consumer._metrics
        if node is not None and consumer.mem_peak:
            # per-operator memory column for EXPLAIN ANALYZE (plain
            # values dict access: node.get() may settle deferred device
            # scalars and accounting must never force a sync)
            prev = node.values.get("mem_peak", 0)
            if consumer.mem_peak > prev:
                node.values["mem_peak"] = consumer.mem_peak

    # -- usage update + arbitration ---------------------------------------

    def _check_watermarks(self, consumer: MemConsumer) -> List[Dict]:
        """Fire pending watermark crossings (lock held).  Peaks are
        monotone and each fraction fires once, so the emitted sequence is
        monotone in the fraction too."""
        fired: List[Dict] = []
        budget = self.effective_budget
        while self._wm_next < len(self._wm_fractions):
            frac = self._wm_fractions[self._wm_next]
            if self.total_used < budget * frac:
                break
            crossing = {"fraction": frac, "used": self.total_used,
                        "budget": budget, "consumer": consumer.name,
                        "at": time.time()}
            self._wm_crossings.append(crossing)
            fired.append(crossing)
            self._wm_next += 1
        return fired

    def _record_spill(self, target: MemConsumer, requester: MemConsumer,
                      path: str, freed: int, wall_ns: int) -> SpillRecord:
        with self._lock:
            rec = SpillRecord(consumer=target.name,
                              requested_by=requester.name, path=path,
                              freed_bytes=int(freed), wall_ns=int(wall_ns),
                              total_used=self.total_used)
            self.num_spills += 1
            self.spill_bytes_freed += rec.freed_bytes
            self.spill_wall_ns += rec.wall_ns
            self._spills_by_path[path] = \
                self._spills_by_path.get(path, 0) + 1
            for i, bound in enumerate(SPILL_HIST_BOUNDS):
                if rec.freed_bytes <= bound:
                    self._spill_hist[i] += 1
                    break
            else:
                self._spill_hist[-1] += 1
            ent = self._by_name.get(target.name)
            if ent is not None:
                ent["spills"] += 1
                ent["freed_bytes"] += rec.freed_bytes
                ent["wall_ns"] += rec.wall_ns
            if target._query_id is not None:
                self._query_ent_locked(target._query_id)["spills"] += 1
            self._spill_records.append(rec)
            if len(self._spill_records) > self.MAX_SPILL_RECORDS:
                del self._spill_records[
                    :len(self._spill_records) - self.MAX_SPILL_RECORDS]
        from auron_tpu.runtime import tracing
        # attribute the spill to the query whose task triggered it (the
        # spill runs on the task's thread, which carries the query's
        # context) — /queries rows stay per-query under concurrency
        tracing.stats_bump("mem_spills")
        tracing.stats_bump("mem_spill_bytes", rec.freed_bytes)
        tracing.event("mem.spill", cat="mem", consumer=rec.consumer,
                      requested_by=rec.requested_by, path=rec.path,
                      freed_bytes=rec.freed_bytes,
                      wall_ms=rec.wall_ns / 1e6)
        return rec

    def _timed_spill(self, target: MemConsumer, requester: MemConsumer,
                     path: str) -> int:
        # spill() re-enters update() (consumers account the batches they
        # shed / re-stage); while it runs on this thread no FURTHER spill
        # may be arbitrated — a nested spill of the same consumer would
        # consume its staged state out from under the outer spill's feet
        # (observed: AggExec._compact_staged mid-spill losing _staged)
        self._tls.spilling = getattr(self._tls, "spilling", 0) + 1
        t0 = time.perf_counter_ns()
        try:
            freed = target.spill()
        finally:
            self._tls.spilling -= 1
        self._record_spill(target, requester, path, freed,
                           time.perf_counter_ns() - t0)
        return freed

    def _pick_spill_victim(self, candidates: List[MemConsumer]
                           ) -> MemConsumer:
        """Rank arbitration victims (lock held).

        `auron.memory.spill.victim.strategy`:

        - ``rate`` (default): prefer the consumer class with the best
          observed freed-bytes-per-wall-second from the spill
          attribution history (`_by_name`) — spilling a consumer that
          historically frees a lot quickly buys the most headroom per
          second of stall, and a "sticky" class that spills slowly or
          frees nothing sinks to the bottom instead of being hammered
          for being big.  Consumers with NO history rank ABOVE every
          measured one (optimistic: unknown classes are tried once so
          they earn a history entry), tie-broken by current size — i.e.
          the no-history fallback IS the classic largest-consumer pick.
        - ``largest``: the reference's pure largest-consumer policy
          (lib.rs:303-423).
        - ``query``: prefer the consumer belonging to the most-over-
          budget QUERY in the per-query ledger (overage against
          `auron.memory.query.budget.bytes`; with no per-query budget
          the ranking degrades to most-total-usage-per-query).  Ties
          break by consumer size.  This is the overload-survival
          policy: arbitration charges the query CAUSING the pressure,
          not whichever consumer class spills fastest.
        """
        strategy = str(conf.get("auron.memory.spill.victim.strategy"))
        if strategy == "largest":
            return max(candidates, key=lambda c: c.mem_used)
        if strategy == "query":
            qbudget = query_budget_bytes()

            def q_rank(c: MemConsumer):
                qid = c._query_id
                if qid is None:
                    # anonymous work sinks below every real query
                    return (float("-inf"), c.mem_used, c.name)
                used = self._queries.get(qid, {}).get("used", 0)
                return (used - qbudget, c.mem_used, c.name)

            return max(candidates, key=q_rank)

        def rank(c: MemConsumer):
            ent = self._by_name.get(c.name)
            if ent and ent.get("spills") and ent.get("wall_ns"):
                rate = ent["freed_bytes"] / ent["wall_ns"]
            else:
                rate = float("inf")   # no history: try it, seed history
            return (rate, c.mem_used, c.name)

        return max(candidates, key=rank)

    def update(self, consumer: MemConsumer, new_bytes: int) -> None:
        """Update usage; may synchronously trigger spills (of this consumer
        or a larger one) to stay under budget — the arbitration loop of
        lib.rs:303-423, extended with per-query budgets: a query over
        `auron.memory.query.budget.bytes` spills its OWN memory even
        while the shared pool is under budget, and is killed past the
        spill grace (`auron.memory.query.kill.grace.spills`)."""
        spill_target: Optional[MemConsumer] = None
        pressure: List[Dict] = []
        fire_pressure: Optional[Tuple] = None
        qid = consumer._query_id
        qbudget = 0
        with self._lock:
            delta = new_bytes - consumer.mem_used
            self.total_used += delta
            consumer.mem_used = new_bytes
            if new_bytes > consumer.mem_peak:
                consumer.mem_peak = new_bytes
            if self.total_used > self.peak_used:
                self.peak_used = self.total_used
            if qid is not None and delta:
                ent = self._query_ent_locked(qid)
                ent["used"] += delta
                if ent["used"] > ent["peak"]:
                    ent["peak"] = ent["used"]
            pressure = self._check_watermarks(consumer)
            hook = self._pressure_hook
            if hook is not None:
                eb = max(1, self.effective_budget)
                if self.total_used > hook[1] * eb:
                    fire_pressure = (hook[0], self.total_used, eb)
            if not getattr(self._tls, "spilling", 0):
                over_pool = self.total_used > self.effective_budget
                qbudget = query_budget_bytes()
                q_over = (qbudget > 0 and qid is not None and
                          self._queries.get(qid, {}).get("used", 0)
                          > qbudget)
                if over_pool or q_over:
                    trigger = min_trigger_size()
                    # only consumers OWNED by this thread are safe to
                    # spill from here: spilling another task's operator
                    # mid-execute would race its buffered state (the
                    # reference's Wait arm covers the cross-task case;
                    # our degenerate form self-spills)
                    me = threading.get_ident()
                    candidates = [
                        c for c in self._consumers
                        if c.spillable and c.mem_used >= trigger and
                        getattr(c, "_owner_thread", me) == me]
                    if q_over and not over_pool:
                        # per-query enforcement relieves the over-budget
                        # query with ITS OWN memory — spilling a
                        # neighbor would punish a query that is inside
                        # its budget
                        candidates = [c for c in candidates
                                      if c._query_id == qid]
                    if candidates:
                        spill_target = self._pick_spill_victim(candidates)
                    # else: over budget but nothing is big enough to
                    # bother — allow (reference returns Nothing below
                    # MIN_TRIGGER_SIZE)
        if pressure:
            from auron_tpu.runtime import tracing
            for p in pressure:
                tracing.event("mem.pressure", cat="mem",
                              fraction=p["fraction"], used=p["used"],
                              budget=p["budget"], consumer=p["consumer"])
        if fire_pressure is not None:
            # outside the lock: the hook takes scheduler-side locks
            fn, used, eb = fire_pressure
            fn(used, eb)
        if spill_target is None:
            return
        # spill outside the lock (spill() re-enters update())
        freed = self._timed_spill(
            spill_target, consumer,
            "arbitration" if spill_target is not consumer else "self")
        if freed <= 0 and spill_target is not consumer and consumer.spillable \
                and consumer.mem_used >= min_trigger_size():
            # fallback path: the chosen target had nothing to give, so the
            # requester spills itself.  This spill was historically never
            # counted (the num_spills bump sat on the arbitration path
            # only); _timed_spill attributes and counts both uniformly.
            self._timed_spill(consumer, consumer, "fallback")
        if qbudget > 0 and qid is not None:
            self._maybe_kill(qid, qbudget)

    def _maybe_kill(self, qid: str, qbudget: int) -> None:
        """After a spill, kill the query if it remains over its budget
        past the spill grace (decision under the lock, hook outside)."""
        grace = kill_grace_spills()
        if grace <= 0:
            return
        reason = None
        with self._lock:
            ent = self._queries.get(qid)
            if (ent is not None and ent["used"] > qbudget and
                    ent["spills"] >= grace and
                    qid not in self._killed_queries):
                self._killed_queries.add(qid)
                ent["kills"] += 1
                reason = (f"query memory budget exceeded: used "
                          f"{ent['used']} > budget {qbudget} after "
                          f"{ent['spills']} spill(s)")
        if reason is not None:
            hook = self._kill_hook or _default_kill_hook
            hook(qid, reason)

    # -- per-query ledger --------------------------------------------------

    def query_usage(self, query_id: str) -> int:
        with self._lock:
            ent = self._queries.get(query_id)
            return ent["used"] if ent is not None else 0

    def query_ledger(self) -> Dict[str, Dict[str, int]]:
        """Per-query usage/peak/spill/kill snapshot — the /memory view
        of WHO holds the pool, and the preemption victim ranking's
        overage source."""
        with self._lock:
            return {qid: dict(ent) for qid, ent in self._queries.items()}

    # -- snapshots ---------------------------------------------------------

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            return {"budget": self.budget, "reserved": self.reserved,
                    "effective_budget": self.effective_budget,
                    "total_used": self.total_used,
                    "peak_used": self.peak_used,
                    "num_consumers": len(self._consumers),
                    "num_spills": self.num_spills,
                    "spill_bytes_freed": self.spill_bytes_freed,
                    "spill_wall_ns": self.spill_wall_ns,
                    "spills_by_path": dict(self._spills_by_path),
                    "watermark_fractions": list(self._wm_fractions),
                    "watermarks_crossed": [dict(c)
                                           for c in self._wm_crossings]}

    def consumer_snapshot(self, top_n: int = 0) -> List[Dict[str, Any]]:
        """Live consumers sorted by current usage (largest first)."""
        with self._lock:
            rows = [{"name": c.name, "used": c.mem_used,
                     "peak": c.mem_peak, "spillable": c.spillable}
                    for c in self._consumers]
        rows.sort(key=lambda r: (-r["used"], -r["peak"], r["name"]))
        return rows[:top_n] if top_n else rows

    def consumer_totals(self) -> Dict[str, Dict[str, int]]:
        """Cumulative per-consumer-name aggregates (peak of peaks, spill
        count/bytes/wall) surviving unregistration — the /memory view of
        which OPERATOR CLASS holds or spills the pool."""
        with self._lock:
            return {name: dict(ent) for name, ent in self._by_name.items()}

    def spill_records(self) -> List[Dict[str, Any]]:
        with self._lock:
            return [r.to_dict() for r in self._spill_records]

    def spill_histogram(self) -> Dict[str, int]:
        """Spill-size histogram over freed bytes, prometheus-style `le`
        upper bounds (cumulative counts are the exporter's job)."""
        with self._lock:
            hist = list(self._spill_hist)
        out = {}
        for bound, n in zip(SPILL_HIST_BOUNDS, hist):
            out[str(bound)] = n
        out["+Inf"] = hist[-1]
        return out


_GLOBAL: Optional[MemManager] = None
_GLOBAL_LOCK = lockcheck.Lock("mem.global")


def _new_manager(budget_bytes: Optional[int]) -> MemManager:
    """Construct a manager with the compat-shim hooks (if any) carried
    over — the pre-fleet module-level semantics for shim users."""
    mgr = MemManager(budget_bytes)
    if _COMPAT_KILL_HOOK is not None:
        mgr.set_kill_hook(_COMPAT_KILL_HOOK)
    if _COMPAT_PRESSURE_HOOK is not None:
        mgr.set_pressure_hook(*_COMPAT_PRESSURE_HOOK)
    return mgr


def get_manager() -> MemManager:
    global _GLOBAL
    with _GLOBAL_LOCK:
        if _GLOBAL is None:
            _GLOBAL = _new_manager(None)
        return _GLOBAL


def reset_manager(budget_bytes: Optional[int] = None) -> MemManager:
    """Test/driver hook: install a fresh manager (e.g. tiny budget for the
    spill fuzz tests, SURVEY §4).  Accounting (peaks, watermarks, spill
    attribution) restarts with the new instance.  Hooks installed via the
    module-level shims are re-applied; per-manager registrations die with
    the old instance (see the overload-hooks comment above)."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        _GLOBAL = _new_manager(budget_bytes)
        return _GLOBAL

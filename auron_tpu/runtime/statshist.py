"""Durable per-plan-signature query statistics (the fifth house member).

lockcheck owns locks, jitcheck owns compiles, wirecheck owns frames,
perfscope owns what the kernels DELIVER — statshist owns what queries
DID, across restarts.  Every statistics surface the engine built before
this module — the `/queries` ring, MemForecaster's last-8 peaks, the
CostModel's live exchange histograms, perfscope's ledgers —
lives in process memory and dies with it, so a restarted server re-pays
every bad first plan and bad first forecast.  This module is the
statistics plane that outlives the process:

- **fold** — at query terminal (session, scheduler, fleet-harvest
  paths; the one funnel is `tracing.record_query`) the QueryRecord's
  wall/queue/exec breakdown, mem peaks, per-exchange observed
  {bytes, rows, partitions}, AQE decisions and the perfscope live
  kernel profile fold into an append-only JSONL store under
  `auron.stats.store.dir` (unset = OFF, terminal path bit-identical).
  Appends are single-`write()` O_APPEND lines so concurrent processes
  on one dir interleave whole records; the load tolerates a torn or
  garbage tail (skip + structured diagnostic, never a crashed load);
  past `auron.stats.compact.max.records` run lines the file is
  rewritten as one EMA summary per signature (count/age-capped).
- **seed** — on first load the store warms the consumers that start
  cold: `MemForecaster` (via `seed_forecaster`, called at
  `AdmissionController` construction — forecasts exist BEFORE the
  first run, marked provenance `store` on /scheduler),
  `adaptive.CostModel`'s per-(signature, exchange) history (exactly
  the learned-initial-plan feed the ROADMAP AQE item names), and the
  perfscope ledger (so /rooflines survives a restart instead of
  re-measuring).
- **regress** — each terminal record is compared to its signature
  baseline (EMA +/- `auron.stats.regression.factor` on wall, exec,
  shuffle bytes, spills, after `auron.stats.regression.min.runs`
  runs); a regression emits ONE structured `query.regression`
  flight-recorder event naming the offending dimensions, bumps
  `auron_query_regressions_total{kind}`, and lands on the bounded
  ring `GET /regressions` serves.  Per-signature history is served at
  `GET /signatures` and `GET /signatures/<sig>`.

Fleet: worker records already ship to the driver over harvest, so the
DRIVER owns the store — `mark_worker()` (executor_endpoint.main)
disarms this module in worker processes.
"""

from __future__ import annotations

import json
import logging
import os
import time
from collections import deque
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

from auron_tpu.runtime import lockcheck

log = logging.getLogger("auron.statshist")

STORE_FILE = "stats.jsonl"
_EMA_ALPHA = 0.3
#: signatures idle longer than this are dropped at compaction/load —
#: the age half of the ISSUE's "count/age caps" (plans change; a
#: signature nobody ran for a month is noise, not a baseline)
MAX_AGE_S = 30 * 24 * 3600.0
#: dimensions the baseline regression check covers, with per-dimension
#: absolute floors so a near-zero EMA (a 2 ms query, an exchange-free
#: plan) cannot flag noise as a regression
_REGRESSION_DIMS: Tuple[Tuple[str, float], ...] = (
    ("wall_s", 0.05), ("exec_s", 0.05),
    ("shuffle_bytes", 1024.0), ("spills", 1.0))
_REGRESSIONS_MAX = 256
_DIAGNOSTICS_MAX = 64
#: how often a non-regressed run refreshes the stored baseline trees
#: (every run would put a full metric-tree dump on the terminal path)
_TREES_REFRESH_RUNS = 8

_LOCK = lockcheck.Lock("statshist")
_WORKER = False          # fleet worker processes never own the store
_LOADED_DIR: Optional[str] = None   # dir the in-memory state mirrors
_RUN_LINES = 0           # run lines in the CURRENT store file (compaction)
_APPENDS = 0
_LOADS = 0
_COMPACTIONS = 0
_CORRUPT_SKIPPED = 0
_SEEDED_COST_MODEL = False
_SEEDED_PERFSCOPE = False
_DEFERRED: set = set()   # query ids whose fold a serving driver owns
_REGRESSIONS: deque = deque(maxlen=_REGRESSIONS_MAX)
_DIAGNOSTICS: deque = deque(maxlen=_DIAGNOSTICS_MAX)


@dataclass
class SigState:
    """One plan signature's durable statistics (in-memory mirror of the
    store: the EMA baseline + the bounded raw tails seeding needs)."""
    signature: str
    runs: int = 0
    first_t: float = 0.0
    last_t: float = 0.0
    ema: Dict[str, float] = field(default_factory=dict)
    last: Dict[str, float] = field(default_factory=dict)
    mem_peaks: deque = field(default_factory=lambda: deque(maxlen=8))
    # ordinal -> {"bytes", "rows", "partitions"} (max-observed: the
    # CostModel's expected_exchange_bytes is a max over history too)
    exchanges: Dict[str, Dict[str, int]] = field(default_factory=dict)
    aqe_kinds: Dict[str, int] = field(default_factory=dict)
    regressions: int = 0
    # merged metric trees of the newest non-regressed run — what
    # /queries/diff?baseline=<sig> diffs a fresh run against
    baseline_trees: Optional[List[Dict[str, Any]]] = None

    def fold(self, dims: Dict[str, float], t: float) -> None:
        self.runs += 1
        self.first_t = self.first_t or t
        self.last_t = max(self.last_t, t)
        for k, v in dims.items():
            prev = self.ema.get(k)
            self.ema[k] = float(v) if prev is None else \
                _EMA_ALPHA * float(v) + (1.0 - _EMA_ALPHA) * prev
            self.last[k] = float(v)

    def to_compact(self) -> Dict[str, Any]:
        doc: Dict[str, Any] = {
            "v": 1, "kind": "compact", "sig": self.signature,
            "runs": self.runs, "t_first": self.first_t,
            "t_last": self.last_t,
            "ema": {k: round(v, 6) for k, v in self.ema.items()},
            "last": {k: round(v, 6) for k, v in self.last.items()},
            "mem_peaks": list(self.mem_peaks),
            "exchanges": self.exchanges,
            "aqe": self.aqe_kinds,
            "regressions": self.regressions}
        if self.baseline_trees is not None:
            doc["trees"] = self.baseline_trees
        return doc

    @classmethod
    def from_compact(cls, doc: Dict[str, Any]) -> "SigState":
        st = cls(signature=str(doc["sig"]))
        st.runs = int(doc.get("runs", 0))
        st.first_t = float(doc.get("t_first", 0.0))
        st.last_t = float(doc.get("t_last", 0.0))
        st.ema = {str(k): float(v)
                  for k, v in (doc.get("ema") or {}).items()}
        st.last = {str(k): float(v)
                   for k, v in (doc.get("last") or {}).items()}
        st.mem_peaks.extend(int(p) for p in doc.get("mem_peaks") or ())
        st.exchanges = {str(k): {kk: int(vv) for kk, vv in v.items()
                                 if vv is not None}
                        for k, v in (doc.get("exchanges") or {}).items()}
        st.aqe_kinds = {str(k): int(v)
                        for k, v in (doc.get("aqe") or {}).items()}
        st.regressions = int(doc.get("regressions", 0))
        st.baseline_trees = doc.get("trees")
        return st


_SIGS: Dict[str, SigState] = {}
_KERN_SITES: Dict[str, Dict[str, float]] = {}   # site -> calls/seconds/bytes


# ---------------------------------------------------------------------------
# arming
# ---------------------------------------------------------------------------

def store_dir() -> str:
    """The armed store directory, or '' (OFF — the default, and always
    in fleet WORKER processes: harvested records fold on the driver)."""
    if _WORKER:
        return ""
    try:
        from auron_tpu.config import conf
        return str(conf.get("auron.stats.store.dir") or "").strip()
    except Exception:  # noqa: BLE001 - config not importable yet
        return ""


def enabled() -> bool:
    return bool(store_dir())


def mark_worker(worker: bool = True) -> None:
    """Disarm the store in fleet worker processes (the driver owns it;
    a worker writing too would double-count every harvested record)."""
    global _WORKER
    _WORKER = worker


# ---------------------------------------------------------------------------
# the on-disk store
# ---------------------------------------------------------------------------

def _store_path(d: str) -> str:
    return os.path.join(d, STORE_FILE)


def _append_line(d: str, doc: Dict[str, Any]) -> None:
    """One whole record per write() on an O_APPEND fd: concurrent
    appenders (two driver processes sharing a dir) interleave records,
    never bytes of records."""
    global _APPENDS, _RUN_LINES
    os.makedirs(d, exist_ok=True)
    data = (json.dumps(doc, sort_keys=True,
                       separators=(",", ":")) + "\n").encode()
    fd = os.open(_store_path(d), os.O_WRONLY | os.O_CREAT | os.O_APPEND,
                 0o644)
    try:
        os.write(fd, data)
    finally:
        os.close(fd)
    _APPENDS += 1
    if doc.get("kind") == "run":
        _RUN_LINES += 1


def _diagnostic(kind: str, detail: str) -> None:
    """Structured load diagnostic: counted, ring-buffered for the
    /signatures page and logged — a corrupt tail is an observation,
    never a crash."""
    global _CORRUPT_SKIPPED
    _CORRUPT_SKIPPED += 1
    _DIAGNOSTICS.append({"kind": kind, "detail": detail[:200],
                         "t": time.time()})
    log.warning("statshist: %s: %s", kind, detail[:200])


def _parse_line(raw: bytes, lineno: int) -> Optional[Dict[str, Any]]:
    s = raw.strip()
    if not s:
        return None
    try:
        doc = json.loads(s)
    except Exception as e:  # noqa: BLE001 - torn/garbage tail
        _diagnostic("corrupt-record",
                    f"line {lineno}: not JSON ({e}): {s[:80]!r}")
        return None
    if not isinstance(doc, dict) or \
            doc.get("kind") not in ("run", "compact", "kern"):
        _diagnostic("corrupt-record",
                    f"line {lineno}: unknown record shape: {s[:80]!r}")
        return None
    if doc["kind"] in ("run", "compact") and not doc.get("sig"):
        _diagnostic("corrupt-record",
                    f"line {lineno}: {doc['kind']} record without sig")
        return None
    return doc


def _apply_run_locked(doc: Dict[str, Any]) -> SigState:
    sig = str(doc["sig"])
    st = _SIGS.get(sig)
    if st is None:
        st = _SIGS[sig] = SigState(signature=sig)
    dims = {str(k): float(v) for k, v in (doc.get("dims") or {}).items()}
    st.fold(dims, float(doc.get("t", 0.0)))
    peak = int(dims.get("mem_peak", 0))
    if peak > 0:
        st.mem_peaks.append(peak)
    for ordn, ex in (doc.get("exchanges") or {}).items():
        cur = st.exchanges.setdefault(str(ordn), {})
        for k in ("bytes", "rows", "partitions"):
            v = ex.get(k)
            if v is not None:
                cur[k] = max(int(cur.get(k, 0)), int(v))
    for kind in doc.get("aqe") or ():
        st.aqe_kinds[str(kind)] = st.aqe_kinds.get(str(kind), 0) + 1
    if doc.get("regressed"):
        st.regressions += 1
    elif doc.get("trees"):
        # a non-regressed run's merged trees become the signature's
        # diff baseline (regressed runs must not poison it)
        st.baseline_trees = doc["trees"]
    return st


def _load_locked(d: str) -> None:
    """Replay the store file into memory (corrupt-tail tolerant: every
    undecodable or mis-shaped line is skipped with a diagnostic)."""
    global _LOADED_DIR, _RUN_LINES, _LOADS
    _SIGS.clear()
    _KERN_SITES.clear()
    _RUN_LINES = 0
    path = _store_path(d)
    now = time.time()
    try:
        with open(path, "rb") as f:
            raw_lines = f.readlines()
    except FileNotFoundError:
        raw_lines = []
    except OSError as e:
        _diagnostic("store-unreadable", f"{path}: {e}")
        raw_lines = []
    for i, raw in enumerate(raw_lines, 1):
        doc = _parse_line(raw, i)
        if doc is None:
            continue
        try:
            if doc["kind"] == "compact":
                st = SigState.from_compact(doc)
                _SIGS[st.signature] = st
            elif doc["kind"] == "run":
                _apply_run_locked(doc)
                _RUN_LINES += 1
            else:  # kern
                _KERN_SITES.clear()
                for site, ent in (doc.get("sites") or {}).items():
                    _KERN_SITES[str(site)] = {
                        "calls": float(ent.get("calls", 0)),
                        "seconds": float(ent.get("seconds", 0.0)),
                        "bytes": float(ent.get("bytes", 0))}
        except Exception as e:  # noqa: BLE001 - one bad record
            _diagnostic("corrupt-record", f"line {i}: {e}")
    # age cap: a signature nobody ran within MAX_AGE_S is dropped
    stale = [s for s, st in _SIGS.items()
             if st.last_t and now - st.last_t > MAX_AGE_S]
    for s in stale:
        del _SIGS[s]
    _LOADED_DIR = d
    _LOADS += 1


def _ensure_loaded() -> Optional[str]:
    """Load (or re-load after a dir change) and run the one-time
    startup seeding of the cost model + perfscope ledger.  Returns the
    armed dir or None."""
    d = store_dir()
    if not d:
        return None
    with _LOCK:
        if _LOADED_DIR != d:
            _load_locked(d)  # lockcheck: waive (replay rebuilds the guarded maps)
    _seed_side_effects()
    return d


def _compact_locked(d: str) -> None:
    """Rewrite the store as one summary line per signature (+ the
    kernel profile line): atomic via temp file + rename.  A concurrent
    appender racing the rename can lose its record to the replaced
    inode — acceptable: the store is statistics, not a ledger, and the
    next terminal re-learns what one lost record knew."""
    global _RUN_LINES, _COMPACTIONS
    path = _store_path(d)
    tmp = f"{path}.{os.getpid()}.tmp"
    with open(tmp, "w") as f:
        for sig in sorted(_SIGS):
            f.write(json.dumps(_SIGS[sig].to_compact(), sort_keys=True,
                               separators=(",", ":")) + "\n")
        if _KERN_SITES:
            f.write(json.dumps(
                {"v": 1, "kind": "kern", "t": time.time(),
                 "sites": _KERN_SITES},
                sort_keys=True, separators=(",", ":")) + "\n")
    os.replace(tmp, path)
    _RUN_LINES = 0
    _COMPACTIONS += 1


# ---------------------------------------------------------------------------
# the terminal fold
# ---------------------------------------------------------------------------

def _record_dims(rec) -> Dict[str, float]:
    """The QueryRecord's wall/queue/exec breakdown + the regression
    dimensions, as one flat dict."""
    from auron_tpu.runtime import tracing
    durations = tracing.timeline_durations(rec.timeline) \
        if rec.timeline else {}
    shuffle_bytes = sum(int(s.get("bytes_out") or 0)
                        for s in rec.exchange_stats or ())
    return {"wall_s": float(rec.wall_s),
            "queue_s": float(durations.get("queued", 0.0)
                             + durations.get("admitted", 0.0)),
            "exec_s": float(durations.get("running", rec.wall_s)),
            "rows": float(rec.rows),
            "mem_peak": float(rec.mem_peak),
            "spills": float(rec.mem_spills),
            "spill_bytes": float(rec.mem_spill_bytes),
            "shuffle_bytes": float(shuffle_bytes)}


def _check_regression_locked(st: SigState, dims: Dict[str, float]
                             ) -> List[Dict[str, Any]]:
    """Offending dimensions of this run vs the signature's EMA
    baseline, BEFORE the run folds in (a run must not soften its own
    baseline)."""
    from auron_tpu.config import conf
    min_runs = int(conf.get("auron.stats.regression.min.runs"))
    if st.runs < max(1, min_runs):
        return []
    factor = max(1.0, float(conf.get("auron.stats.regression.factor")))
    offending = []
    for dim, floor in _REGRESSION_DIMS:
        base = st.ema.get(dim)
        if base is None:
            continue
        threshold = max(base * factor, floor)
        if dims.get(dim, 0.0) > threshold:
            offending.append({"dim": dim,
                              "observed": round(dims[dim], 6),
                              "baseline": round(base, 6),
                              "threshold": round(threshold, 6)})
    return offending


def _kern_profile_slice() -> Dict[str, Dict[str, float]]:
    """The perfscope ledger's per-site totals (calls/seconds/bytes) —
    the store's kernel-profile record, refreshed at each terminal so
    the ledger can be re-seeded after restart."""
    from auron_tpu.runtime import perfscope
    out: Dict[str, Dict[str, float]] = {}
    for site, ent in perfscope.snapshot().items():
        if ent.get("calls"):
            out[site] = {"calls": float(ent["calls"]),
                         "seconds": float(ent["seconds"]),
                         "bytes": float(ent["bytes"])}
    return out


def defer(query_id: str) -> None:
    """Mark a query whose fold a serving driver owns: the session-level
    `record_query` fires with a minimal running->terminal timeline, the
    scheduler re-folds after patching the full lifecycle machine in —
    deferral keeps it to ONE fold with the richer record."""
    if not enabled():
        return
    with _LOCK:
        _DEFERRED.add(query_id)


def observe_deferred(query_id: str, rec) -> None:
    """The serving driver's half of `defer`: fold the patched record."""
    with _LOCK:
        was_deferred = query_id in _DEFERRED
        _DEFERRED.discard(query_id)
    if rec is not None and was_deferred:
        on_record(rec)


def on_record(rec) -> None:
    """Fold one terminal QueryRecord into the store (the
    `tracing.record_query` hook).  OFF (dir unset) or an unsigned /
    failed / deferred record: no-op."""
    if rec.error or not getattr(rec, "signature", ""):
        return
    d = store_dir()
    if not d:
        return
    with _LOCK:
        if rec.query_id in _DEFERRED:
            return   # the serving driver re-folds with the full record
    _ensure_loaded()
    from auron_tpu.config import conf
    from auron_tpu.runtime import counters
    dims = _record_dims(rec)
    kern = _kern_profile_slice()
    compact_after = False
    with _LOCK:
        st = _SIGS.get(rec.signature)
        if st is None:
            st = _SIGS[rec.signature] = SigState(signature=rec.signature)
        offending = _check_regression_locked(st, dims)
        doc: Dict[str, Any] = {
            "v": 1, "kind": "run", "sig": rec.signature,
            "qid": rec.query_id,
            "t": float(rec.started_at or time.time()),
            "dims": {k: round(v, 6) for k, v in dims.items()}}
        if rec.exchange_stats:
            doc["exchanges"] = {
                str(s.get("exchange")): {
                    "bytes": int(s.get("bytes_out") or 0),
                    "rows": int(s.get("rows_out") or 0),
                    "partitions": int(s.get("partitions") or 0)}
                for s in rec.exchange_stats if s.get("exchange")}
        if rec.aqe_decisions:
            doc["aqe"] = [str(a.get("kind")) for a in rec.aqe_decisions]
        if offending:
            doc["regressed"] = [o["dim"] for o in offending]
        elif rec.metric_trees and (
                st.baseline_trees is None
                or (st.runs + 1) % _TREES_REFRESH_RUNS == 0):
            # serializing the full merged trees every terminal is the
            # dominant armed cost — refresh the diff baseline only
            # when missing or every Nth run (the <2% overhead gate)
            doc["trees"] = rec.metric_trees
        try:
            _append_line(d, doc)
        except OSError as e:
            _diagnostic("append-failed", f"{d}: {e}")
        _apply_run_locked(doc)
        if kern:
            _KERN_SITES.clear()
            _KERN_SITES.update(kern)
            try:
                _append_line(d, {"v": 1, "kind": "kern",
                                 "t": time.time(), "sites": kern})
            except OSError as e:
                _diagnostic("append-failed", f"{d}: {e}")
        limit = max(8, int(conf.get("auron.stats.compact.max.records")))
        if _RUN_LINES > limit:
            compact_after = True
            try:
                _compact_locked(d)  # lockcheck: waive (atomic rewrite of guarded state)
            except OSError as e:
                _diagnostic("compact-failed", f"{d}: {e}")
        if offending:
            entry = {"t": time.time(), "query_id": rec.query_id,
                     "signature": rec.signature,
                     "wall_s": round(rec.wall_s, 4),
                     "dims": offending}
            _REGRESSIONS.append(entry)
    if compact_after:
        counters.bump("stats_compactions")
    if offending:
        from auron_tpu.runtime import events
        names = ", ".join(
            f"{o['dim']} {o['observed']:g} > {o['threshold']:g} "
            f"(ema {o['baseline']:g})" for o in offending)
        events.emit("query.regression",
                    f"query {rec.query_id} regressed vs signature "
                    f"{rec.signature} baseline: {names}",
                    [rec.query_id], signature=rec.signature,
                    dims=[o["dim"] for o in offending],
                    detail=offending)
        for o in offending:
            counters.bump(f"query_regressions_{o['dim']}")


# ---------------------------------------------------------------------------
# startup seeding (the consumers that used to start cold)
# ---------------------------------------------------------------------------

def seed_forecaster(forecaster) -> int:
    """Warm a MemForecaster from the store (called at
    AdmissionController construction): per signature, the recent
    observed mem peaks — so the FIRST admission of a known plan shape
    forecasts from history instead of the configured default.  Returns
    the number of signatures seeded."""
    if _ensure_loaded() is None:
        return 0
    with _LOCK:
        peaks = {sig: list(st.mem_peaks)
                 for sig, st in _SIGS.items() if st.mem_peaks}
    n = 0
    for sig, ps in peaks.items():
        if forecaster.seed(sig, ps):
            n += 1
    return n


def _seed_side_effects() -> None:
    """One-time per load: warm the CostModel's exchange history (the
    learned-initial-plan feed) and the perfscope ledger (calibration
    survives restart).  Both seeds yield to live observations: they
    never overwrite a key that already has history."""
    global _SEEDED_COST_MODEL, _SEEDED_PERFSCOPE
    with _LOCK:
        do_cost = not _SEEDED_COST_MODEL and bool(_SIGS)
        do_perf = not _SEEDED_PERFSCOPE and bool(_KERN_SITES)
        if do_cost:
            _SEEDED_COST_MODEL = True
            exchanges = [(sig, ordn, dict(ex))
                         for sig, st in _SIGS.items()
                         for ordn, ex in st.exchanges.items()]
        if do_perf:
            _SEEDED_PERFSCOPE = True
            kern = {site: dict(ent)
                    for site, ent in _KERN_SITES.items()}
    if do_cost and exchanges:
        from auron_tpu.runtime.adaptive import unified_cost_model
        model = unified_cost_model()
        for sig, ordn, ex in exchanges:
            model.seed_exchange(sig, ordn, ex.get("bytes", 0),
                                ex.get("rows", 0))
    if do_perf and kern:
        from auron_tpu.runtime import perfscope
        seen = perfscope.snapshot()
        for site, ent in kern.items():
            if site in seen:
                continue   # live observations beat the seed
            perfscope.record(site, float(ent.get("seconds", 0.0)),
                             int(ent.get("bytes", 0)),
                             signature="<store>")


# ---------------------------------------------------------------------------
# views (the /signatures, /regressions and Prometheus surfaces)
# ---------------------------------------------------------------------------

def signatures_snapshot() -> Dict[str, Dict[str, Any]]:
    """{sig: summary} for GET /signatures."""
    _ensure_loaded()
    with _LOCK:
        return {sig: {"runs": st.runs, "last_at": st.last_t,
                      "ema_wall_s": round(st.ema.get("wall_s", 0.0), 4),
                      "ema_mem_peak": int(st.ema.get("mem_peak", 0)),
                      "exchanges": len(st.exchanges),
                      "regressions": st.regressions,
                      "has_baseline_trees":
                          st.baseline_trees is not None}
                for sig, st in sorted(_SIGS.items())}


def signature_detail(sig: str) -> Optional[Dict[str, Any]]:
    """Full per-signature history doc for GET /signatures/<sig>."""
    _ensure_loaded()
    with _LOCK:
        st = _SIGS.get(sig)
        if st is None:
            return None
        doc = st.to_compact()
        doc.pop("trees", None)
        doc["has_baseline_trees"] = st.baseline_trees is not None
        doc["recent_regressions"] = [dict(r) for r in _REGRESSIONS
                                     if r["signature"] == sig]
    return doc


def baseline_trees(sig: str) -> Optional[List[Dict[str, Any]]]:
    """The stored merged metric trees of the signature's newest
    non-regressed run (the /queries/diff?baseline= right-hand side)."""
    _ensure_loaded()
    with _LOCK:
        st = _SIGS.get(sig)
        return None if st is None else st.baseline_trees


def regressions_snapshot() -> List[Dict[str, Any]]:
    with _LOCK:
        return [dict(r) for r in _REGRESSIONS]


def diagnostics() -> List[Dict[str, Any]]:
    with _LOCK:
        return [dict(d) for d in _DIAGNOSTICS]


def store_stats() -> Dict[str, int]:
    """Store totals for counters.snapshot() and the /metrics gauges."""
    d = store_dir()
    size = 0
    if d:
        _ensure_loaded()
        try:
            size = os.path.getsize(_store_path(d))
        except OSError:
            size = 0
    with _LOCK:
        return {"store_signatures": len(_SIGS) if d else 0,
                "store_bytes": int(size),
                "store_appends": _APPENDS,
                "store_loads": _LOADS,
                "store_compactions": _COMPACTIONS,
                "store_corrupt_skipped": _CORRUPT_SKIPPED}


def reset_state() -> None:
    """Test hook: forget the in-memory mirror and seeding marks (the
    on-disk store persists — that is the point)."""
    global _LOADED_DIR, _RUN_LINES, _APPENDS, _LOADS, _COMPACTIONS, \
        _CORRUPT_SKIPPED, _SEEDED_COST_MODEL, _SEEDED_PERFSCOPE
    with _LOCK:
        _SIGS.clear()
        _KERN_SITES.clear()
        _DEFERRED.clear()
        _REGRESSIONS.clear()
        _DIAGNOSTICS.clear()
        _LOADED_DIR = None
        _RUN_LINES = 0
        _APPENDS = _LOADS = _COMPACTIONS = _CORRUPT_SKIPPED = 0
        _SEEDED_COST_MODEL = _SEEDED_PERFSCOPE = False

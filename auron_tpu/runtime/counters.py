"""Process-level runtime counters — the ONE place task/query lifecycle
totals live.

Before this module the executor kept private `_TASKS_*` globals that
`profiling._metrics_snapshot` read via `getattr(..., 0)` — a rename away
from silently reporting zero forever (and `tasks_completed` was indeed
dangling for a while).  Now the executor, the task pool and the session
increment named counters here, and both the Prometheus `/metrics` view
and the `/queries` page read the same snapshot.  `runtime/retry.py`
keeps its own attempt/retry/fallback stats (they pre-date this module
and the chaos sweep diffs them); `snapshot()` folds both sources into
one flat dict so consumers never chase two registries.
"""

from __future__ import annotations

from typing import Dict, List, Tuple

from auron_tpu.runtime import lockcheck

__all__ = ["bump", "get", "snapshot", "reset", "observe", "histograms"]

_LOCK = lockcheck.Lock("counters")
_COUNTERS: Dict[str, int] = {
    "tasks_started": 0,
    "tasks_completed": 0,
    "tasks_failed": 0,
    "tasks_retried": 0,
    "queries_started": 0,
    "queries_completed": 0,
    "queries_failed": 0,
    # serving tier (auron_tpu.serving): submissions + admission outcomes
    "queries_submitted": 0,
    "queries_cancelled": 0,
    "admission_admitted": 0,
    "admission_queued": 0,
    "admission_shed": 0,
    "admission_degraded": 0,
    # overload survival: preemptive kill-and-requeue (task_pool
    # .preempt_query / QueryScheduler requeue path)
    "preemptions": 0,
    "requeues": 0,
    # executor fleet (serving/fleet.py): multi-process serving —
    # dispatches to executors, executor deaths declared by the health
    # machine, and cross-process kill-and-requeue events
    "fleet_submissions": 0,
    "fleet_dispatches": 0,
    "fleet_completions": 0,
    "fleet_deaths": 0,
    "fleet_requeues": 0,
    # elastic fleet sizing (queue-depth scale-up / idle retirement)
    "fleet_scale_ups": 0,
    "fleet_scale_downs": 0,
    # live-heartbeat admission re-forecasts (grow/shrink of a running
    # query's reservation from worker memory telemetry)
    "admission_reforecasts": 0,
    # durable shuffle (shuffle_rss/durable.py + the session's
    # commit-protocol exchange): stages resumed from committed side-car
    # manifests instead of recomputed, per-map skip/run splits, fetch
    # regenerations (targeted re-dispatch after an integrity failure),
    # and degrades back to executor-local shuffle
    "rss_stage_skips": 0,
    "rss_map_tasks_skipped": 0,
    "rss_map_tasks_run": 0,
    "rss_fetch_regens": 0,
    "rss_degrades": 0,
    "rss_sidecar_deaths": 0,
    "rss_cleanups": 0,
    # data plane (PR 14): exchange bytes through the shuffle writers /
    # readers (all transports), for the dataplane_check gate
    "shuffle_bytes_pushed": 0,
    "shuffle_bytes_fetched": 0,
    # adaptive execution (runtime/adaptive.py): stage-boundary replan
    # decisions that FIRED — broadcast-vs-shuffle join conversions,
    # reduce partition coalesces, skew splits (tools/aqe_check.sh
    # asserts all three via prom_assert)
    "adaptive_broadcast": 0,
    "adaptive_coalesce": 0,
    "adaptive_skew_split": 0,
    # tracing: spans dropped past auron.trace.max.events (per-recorder
    # `dropped` counts feed trace_truncated on the exported trace; this
    # is the process total `auron_trace_dropped_events_total` exports)
    "trace_dropped_events": 0,
    # wire-protocol contract layer (runtime/wirecheck.py): peers
    # refused by the version handshake (`auron_wire_rejects_total`);
    # per-(wire,cmd) frame counts fold in from wirecheck.frame_counts()
    "wire_rejects": 0,
}

# -- latency histograms (the /metrics `auron_query_*_seconds` family) -------
#
# Fixed-bucket seconds histograms in the Prometheus exposition shape
# (cumulative `_bucket{le=}` counts + `_sum` + `_count`).  Pre-seeded
# names always appear on /metrics — a scrape target that only exists
# once a query has run is a dashboard hole.

_HIST_BUCKETS: Tuple[float, ...] = (
    0.005, 0.02, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0,
    10.0, 30.0, 60.0, 120.0, 300.0)
_HIST_NAMES = ("query_wall_seconds", "query_queue_wait_seconds",
               "query_admission_wait_seconds", "query_exec_seconds")
_HISTS: Dict[str, Dict[str, object]] = {
    name: {"counts": [0] * (len(_HIST_BUCKETS) + 1),
           "sum": 0.0, "count": 0}
    for name in _HIST_NAMES
}


def observe(name: str, value: float) -> None:
    """Record one observation into the named seconds histogram (created
    on first use for non-preseeded names)."""
    v = float(value)
    with _LOCK:
        h = _HISTS.get(name)
        if h is None:
            h = _HISTS[name] = {
                "counts": [0] * (len(_HIST_BUCKETS) + 1),
                "sum": 0.0, "count": 0}
        idx = len(_HIST_BUCKETS)
        for i, le in enumerate(_HIST_BUCKETS):
            if v <= le:
                idx = i
                break
        h["counts"][idx] += 1          # type: ignore[index]
        h["sum"] += v                  # type: ignore[operator]
        h["count"] += 1                # type: ignore[operator]


def histograms() -> Dict[str, Dict[str, object]]:
    """{name: {"buckets": [(le, cumulative_count)], "sum", "count"}} —
    cumulative per-bucket counts, ready for text-format exposition."""
    with _LOCK:
        out: Dict[str, Dict[str, object]] = {}
        for name, h in _HISTS.items():
            cum = 0
            buckets: List[Tuple[float, int]] = []
            for le, c in zip(_HIST_BUCKETS, h["counts"]):  # type: ignore
                cum += c
                buckets.append((le, cum))
            out[name] = {"buckets": buckets,
                         "sum": float(h["sum"]),      # type: ignore[arg-type]
                         "count": int(h["count"])}    # type: ignore[arg-type]
        return out


def bump(key: str, delta: int = 1) -> int:
    with _LOCK:
        _COUNTERS[key] = _COUNTERS.get(key, 0) + int(delta)
        return _COUNTERS[key]


def get(key: str) -> int:
    with _LOCK:
        return _COUNTERS.get(key, 0)


def snapshot() -> Dict[str, int]:
    """Flat counter snapshot: lifecycle counters here + the retry-policy
    stats (prefixed `retry_`) + per-site jit compile counts (prefixed
    `jit_compiles_`, runtime/jitcheck.py) + per-(wire,cmd) frame counts
    (prefixed `wire_frames_`, runtime/wirecheck.py) + the durable
    stats-store totals (prefixed `stats_`, runtime/statshist.py) so
    `/metrics` exports one namespace."""
    from auron_tpu.runtime import jitcheck, retry, statshist, wirecheck
    with _LOCK:
        out = dict(_COUNTERS)
    for k, v in retry.stats_snapshot().items():
        out[f"retry_{k}"] = v
    for site, n in jitcheck.compile_counts().items():
        out[f"jit_compiles_{site}"] = n
    for (wire, cmd), n in wirecheck.frame_counts().items():
        out[f"wire_frames_{wire}_{cmd}"] = n
    for k, v in statshist.store_stats().items():
        out[f"stats_{k}"] = v
    return out


def reset() -> None:
    """Test hook: zero the lifecycle counters and histograms (retry
    stats have their own reset)."""
    with _LOCK:
        for k in _COUNTERS:
            _COUNTERS[k] = 0
        for h in _HISTS.values():
            h["counts"] = [0] * (len(_HIST_BUCKETS) + 1)
            h["sum"] = 0.0
            h["count"] = 0

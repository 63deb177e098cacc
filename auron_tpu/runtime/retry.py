"""Shared retry policy: classification, capped backoff, attempt budgets.

One policy consumed by every recovery site — task_pool.run_tasks (per-task
replay), the RSS shuffle clients (replacing the hand-rolled reconnect in
shuffle_rss/celeborn.py), the engine-service client, the kafka consumer
and the SPMD degradation tier — so "what is retryable" and "how long do
we back off" can never drift between subsystems (the role Spark's single
TaskScheduler retry policy plays for the reference).

Classification is a two-way split:

- **retryable-IO**: transport/transient errors — ConnectionError,
  TimeoutError, EOFError, generic OSError (a peer restart, a dropped
  socket), injected io/timeout faults, and anything flagged
  ``auron_retryable = True`` (the device-fault tier, retryable
  SpmdGuardTripped).  Deterministic OSError subclasses (FileNotFoundError,
  PermissionError, ...) are excluded: re-reading a missing file fails
  identically forever.
- **deterministic**: everything else (ValueError, RuntimeError, plan
  verification errors, injected `error` faults) — retrying replays the
  same failure, so it ferries immediately.  Wire-contract violations
  (`wirecheck.WirecheckError`, the RSS server's in-band protocol
  errors, version-handshake refusals) declare
  ``auron_deterministic = True``: a malformed or refused frame fails
  identically on every replay, so no retry tier ever spins on it.

WHICH commands may sit inside a replaying tier at all is declared in
the wirecheck registry (runtime/wirecheck.py, idempotency classes) and
statically enforced by `python -m auron_tpu.analysis --protocol` — a
non-replayable command dispatched through `call_with_retry` without a
dedup token is a CI error, not a review comment.

Backoff is capped exponential with *seeded* jitter: attempt N sleeps
``min(base * 2**N, max) * (1 + jitter * u)`` with ``u`` drawn from a
``random.Random(seed)`` stream per call — two runs with the same seed
produce byte-identical schedules (the chaos sweep depends on this).

Budget exhaustion re-raises the ORIGINAL error with the attempt history
attached (``exc.auron_attempts``) and marks it consumed
(``exc.auron_retry_exhausted``) so an outer retry site never multiplies
attempts — nested policies compose additively, not geometrically (the
"no retry storms" bound in the chaos acceptance gate).
"""

from __future__ import annotations

import logging
import random
import time
from dataclasses import dataclass
from typing import Any, Callable, Dict, Optional

from auron_tpu.config import conf
from auron_tpu.runtime import lockcheck

log = logging.getLogger("auron_tpu.retry")

__all__ = [
    "RetryPolicy", "is_retryable", "task_classify", "call_with_retry",
    "stats_snapshot", "reset_stats", "add_fallback", "add_retry",
]

# deterministic OSError subclasses: the path/permission is wrong, not the
# weather — replaying cannot help
_DETERMINISTIC_OSERRORS = (
    FileNotFoundError, PermissionError, FileExistsError,
    IsADirectoryError, NotADirectoryError,
)


def is_retryable(exc: BaseException) -> bool:
    """The classification table (see module docstring)."""
    if getattr(exc, "auron_deterministic", False):
        return False      # declared never-retryable (QueryCancelled:
        #                   a preempted query must not consume retry
        #                   budgets — its requeue re-arms them fresh)
    if getattr(exc, "auron_retry_exhausted", False):
        return False      # an inner policy already spent the budget
    if getattr(exc, "auron_retryable", False):
        return True       # device-fault tier / retryable guard trips
    if isinstance(exc, _DETERMINISTIC_OSERRORS):
        return False
    return isinstance(exc, (OSError, EOFError))


def task_classify(exc: BaseException) -> bool:
    """The TASK tier's classifier (run_tasks): a full task replay re-runs
    from scratch, so inner per-RPC budgets re-arm — an IO error that
    exhausted a push/fetch retry is still worth one task replay (Spark's
    task-retry-over-whatever-failed-inside model; composition stays
    bounded: inner budget x task budget, both fixed).  Device-tier
    errors keep respecting the exhausted marker — the executor's inner
    re-executions already count as task attempts, so replaying them
    again would break the chaos sweep's attempts <= 3x bound."""
    if getattr(exc, "auron_deterministic", False):
        return False      # QueryCancelled-family: never a task replay
    if getattr(exc, "auron_retryable", False):
        return not getattr(exc, "auron_retry_exhausted", False)
    if isinstance(exc, _DETERMINISTIC_OSERRORS):
        return False
    return isinstance(exc, (OSError, EOFError))


@dataclass(frozen=True)
class RetryPolicy:
    """Attempt budget + backoff schedule; `seed` fixes the jitter."""

    max_attempts: int = 3
    backoff_base_s: float = 0.025
    backoff_max_s: float = 1.0
    jitter: float = 0.25
    seed: int = 0

    @classmethod
    def from_conf(cls, max_attempts: Optional[int] = None) -> "RetryPolicy":
        return cls(
            max_attempts=(max_attempts if max_attempts is not None
                          else int(conf.get("auron.retry.max.attempts"))),
            backoff_base_s=float(
                conf.get("auron.retry.backoff.base.ms")) / 1000.0,
            backoff_max_s=float(
                conf.get("auron.retry.backoff.max.ms")) / 1000.0,
            jitter=float(conf.get("auron.retry.jitter")),
            seed=int(conf.get("auron.retry.seed")))

    @classmethod
    def task_policy(cls) -> "RetryPolicy":
        """Per-task replay budget: 1 + auron.task.retries attempts (the
        Spark task-retry model; 0 retries by default)."""
        return cls.from_conf(
            max_attempts=1 + int(conf.get("auron.task.retries")))

    def backoff_s(self, attempt: int, rng: random.Random) -> float:
        """Sleep before re-running `attempt` (1-based retry index):
        capped exponential, seeded jitter, always within
        [0, backoff_max_s * (1 + jitter)]."""
        base = min(self.backoff_base_s * (2 ** max(attempt - 1, 0)),
                   self.backoff_max_s)
        return base * (1.0 + self.jitter * rng.random())


# process-wide recovery counters — the chaos sweep reads deltas of these
# for its run report ("num_retries / num_fallbacks visible")
_STATS_LOCK = lockcheck.Lock("retry.stats")
_STATS: Dict[str, int] = {"attempts": 0, "retries": 0, "exhausted": 0,
                          "fallbacks": 0}


def _bump(key: str, delta: int = 1) -> None:
    with _STATS_LOCK:
        _STATS[key] = _STATS.get(key, 0) + delta


def add_fallback(n: int = 1) -> None:
    """Record a degradation event (SPMD -> serial path)."""
    _bump("fallbacks", n)
    from auron_tpu.runtime import tracing
    tracing.stats_bump("fallbacks", n)
    tracing.event("fallback", cat="retry", tier="spmd->serial")


def add_retry(n: int = 1) -> None:
    """Record re-execution events that bypass call_with_retry (the SPMD
    stage driver's guard-trip / device-fault re-runs)."""
    _bump("retries", n)
    from auron_tpu.runtime import tracing
    tracing.stats_bump("retries", n)
    tracing.event("retry", cat="retry", tier="spmd-stage")


def stats_snapshot() -> Dict[str, int]:
    with _STATS_LOCK:
        return dict(_STATS)


def reset_stats() -> None:
    with _STATS_LOCK:
        for k in _STATS:
            _STATS[k] = 0


def call_with_retry(fn: Callable[[], Any],
                    policy: Optional[RetryPolicy] = None,
                    label: str = "",
                    classify: Callable[[BaseException], bool] = is_retryable,
                    on_retry: Optional[Callable[[int, BaseException],
                                                None]] = None,
                    sleep: Callable[[float], None] = time.sleep) -> Any:
    """Run `fn` under the policy.

    Retryable failures re-run after a backoff; deterministic failures
    (per `classify`) and budget exhaustion re-raise the original error
    with ``auron_attempts`` — a tuple of (attempt, exception summary,
    backoff seconds) — attached, plus ``auron_retry_exhausted`` when the
    budget ran out, so outer retry sites ferry instead of multiplying.
    `on_retry(next_attempt, exc)` fires before each re-run (metric
    hooks)."""
    if policy is None:
        policy = RetryPolicy.from_conf()
    rng = random.Random(policy.seed)
    history: list = []
    attempts = max(1, policy.max_attempts)
    for attempt in range(1, attempts + 1):
        _bump("attempts")
        try:
            return fn()
        except BaseException as e:  # noqa: BLE001 - classified below
            retryable = classify(e)
            if retryable and attempt < attempts:
                delay = policy.backoff_s(attempt, rng)
                history.append((attempt, f"{type(e).__name__}: {e}",
                                round(delay, 6)))
                _bump("retries")
                # the re-execution is a span EVENT carrying the
                # classified error (runtime/tracing.py): a traced chaos
                # run shows exactly which attempt re-drew which fault
                from auron_tpu.runtime import tracing
                tracing.stats_bump("retries")
                tracing.event("retry", cat="retry", label=label or "call",
                              attempt=attempt,
                              error=f"{type(e).__name__}: {e}",
                              backoff_s=round(delay, 6))
                if on_retry is not None:
                    on_retry(attempt + 1, e)
                log.warning("%s failed (attempt %d/%d, %s): %s; "
                            "retrying in %.3fs",
                            label or "call", attempt, attempts,
                            type(e).__name__, e, delay)
                if delay > 0:
                    # backoff sleeps are a known blocking surface: a
                    # retry loop entered with a lock held would stall
                    # every peer of that lock for the whole schedule
                    lockcheck.blocked("retry.backoff")
                    sleep(delay)
                continue
            history.append((attempt, f"{type(e).__name__}: {e}", 0.0))
            e.auron_attempts = tuple(history)   # type: ignore[attr-defined]
            if retryable:
                # budget exhausted on a retryable error: mark it spent so
                # outer sites don't retry the retries
                e.auron_retry_exhausted = True  # type: ignore[attr-defined]
                _bump("exhausted")
            raise
    raise AssertionError("unreachable")   # pragma: no cover

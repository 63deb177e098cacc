"""Global jitted-kernel cache + the single-sync policy.

The reference keeps one long-lived native runtime per executor process and
compiles nothing per task; round 1 of this engine rebuilt every operator's
jit cache per `execute_plan` call, so every task re-traced every kernel.
This module is the fix: jitted kernels live at module scope, keyed by the
*static structure* that determines the traced program (jax.jit's own cache
then keys on avals/pytree structure), so a repeated query shape executes
with zero re-tracing — the analogue of the reference running pre-compiled
Rust code per task (rt.rs:76-139).

Single-sync policy: operators fetch device results to host only through
`host_sync` (one fetch per operator per batch — typically the output row
count).  Tests wrap pipelines in `jax.transfer_guard("disallow")` and count
`host_sync` calls, which both catches stray implicit transfers and enforces
the <=1-sync budget (the per-batch-host-round-trip problem the reference
avoids with its mpsc(1) pipeline, rt.rs:141-238).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Hashable, Tuple

import jax

from auron_tpu.runtime import jitcheck, tracing

_CACHE: Dict[Hashable, Any] = {}
_STATS = {"hits": 0, "misses": 0}
_FAMILY_BUILDS: Dict[str, int] = {}


def _family(key: Hashable) -> str:
    """Kernel family = the leading string of a structured cache key
    ("agg.group_reduce", "join.range", ...) — the granularity
    family_builds reports builds by."""
    if isinstance(key, tuple) and key and isinstance(key[0], str):
        return key[0]
    return str(key)


def cached_jit(key: Hashable, builder: Callable[[], Callable],
               static_argnames: Tuple[str, ...] = ()) -> Callable:
    """Return the module-global jitted kernel for `key`, building it on
    first use.  `builder()` must return a pure function of jax pytrees;
    differing input shapes/structures are handled by jax.jit's own cache
    under the same key."""
    fn = _CACHE.get(key)
    if fn is None:
        _STATS["misses"] += 1
        fam = _family(key)
        _FAMILY_BUILDS[fam] = _FAMILY_BUILDS.get(fam, 0) + 1
        # the kernel family IS the jit-site name: every cached_jit
        # program funnels through the jitcheck registry, so per-family
        # compile counts land in /metrics and the compile manifest
        fn = jitcheck.site(fam).jit(builder(),
                                    static_argnames=static_argnames)
        _CACHE[key] = fn
        # a miss is a new jitted program: mark the build point in the
        # trace (jax compiles lazily at first call, so this is an
        # instant, not a duration — fragment.compile/spmd.compile carry
        # the durations)
        tracing.event("kernel.build", cat="compile")
    else:
        _STATS["hits"] += 1
    return fn


def host_sync(x: Any) -> Any:
    """The sanctioned device->host fetch (see module docstring).  Returns
    numpy/python values; accepts any pytree (fetched as one unit so a
    packed scalar pair costs one round trip).  Each call is a blocking
    round trip, counted for the ambient query (`host_syncs`)."""
    jitcheck.note_sync("host_sync")
    tracing.stats_bump("host_syncs")
    with jax.transfer_guard("allow"):
        return jax.device_get(x)


def cache_info() -> Dict[str, int]:
    """Cache observability: resident kernel count plus cumulative lookup
    hits/misses (misses == builds).  The task runtime snapshots these
    around each task and reports the deltas in the metric tree."""
    return {"kernels": len(_CACHE), "hits": _STATS["hits"],
            "misses": _STATS["misses"]}


def family_builds() -> Dict[str, int]:
    """Cumulative kernel BUILDS by family.  Copy, not view."""
    return dict(_FAMILY_BUILDS)


def clear() -> None:
    """Test hook: drop every cached kernel (forces re-tracing)."""
    _CACHE.clear()
    _STATS["hits"] = _STATS["misses"] = 0
    _FAMILY_BUILDS.clear()

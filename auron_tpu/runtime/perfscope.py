"""Per-jit-site performance ledger (perfscope) — kernel seconds & bytes.

The fourth house-pattern member: lockcheck watches locks, jitcheck
watches compiles, wirecheck watches frames — perfscope watches what the
compiled programs actually DELIVER.  The ROADMAP's standing headline
(every kernel <= 3.7 GB/s achieved) was only visible in offline bench
runs; in production nothing said which site was at the roof and which
was at the dispatch floor.  Flare's case (PAPERS.md) is that native
query acceleration lives or dies by instrumented per-kernel throughput
against the hardware roof; HiFrames' is that observed execution should
drive the next plan.  This module makes both live:

- every program built through the jitcheck site registry is wrapped in
  a timing shim (`wrap`); ARMED, each execution records wall seconds +
  estimated bytes per (site, abstract signature) into a bounded
  per-site ledger (reservoir ring + EMA + running totals);
- BYTES are estimated per kernel family from the input/output buffer
  avals (shape x itemsize, the roofline convention: read input once +
  write output once); families with a different algorithmic byte count
  declare their own estimator (`declare_estimator`);
- achieved GB/s is computed against a MACHINE PEAK measured once by a
  STREAM-style memcpy probe and cached to disk — `rooflines()` is the
  table /rooflines and the report CLI render.

COST CONTRACT: off by default.  Disarmed, the shim is ONE module-flag
read + one indirect call per kernel execution (same class of cost as a
`tracing.span` site with no recorder) — gated by the interleaved warm
q01 A/B in tools/perf_check.sh (< 2%).  Arming is a RUNTIME decision
(`configure(True)` / `auron.perf.enable` / the env fallback
``AURON_TPU_AURON_PERF_ENABLE``), unlike jitcheck's wrap-time one: the
shim is always installed, so a long-lived process can be armed live.
"""

from __future__ import annotations

import contextvars
import fnmatch
import json
import os
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from auron_tpu.runtime import lockcheck

__all__ = [
    "wrap", "enabled", "configure", "record", "declare_estimator",
    "estimator_for", "snapshot", "rooflines", "kernel_seconds",
    "kernel_bytes",
    "machine_peak_gbps", "measure_peak", "device_peak_gbps",
    "DEVICE_PEAK_GBPS", "attribution_scope",
    "reset_state", "render_report",
]


def _env_bool(key: str, default: bool = False) -> bool:
    raw = os.environ.get(key)
    if raw is None:
        return default
    return raw.strip().lower() in ("1", "true", "yes", "on")


# decided at import from the env fallback, flipped at runtime by
# configure(): the shim consults this ONE flag per execution
_ARMED = _env_bool("AURON_TPU_AURON_PERF_ENABLE")

# leaf-only guard (never held across a conf read or a device sync)
_LOCK = lockcheck.Lock("perfscope")

# armed-path parameters, cached at configure() time: the shim must not
# pay a conf.get (scoped-dict walk) per kernel execution — re-arm after
# changing auron.perf.* under conf.scoped to pick the new values up
_SYNC = True
_CAP = 64
_ALPHA = 0.2
_MAX_SIGS = 8
_STRIDE = 8   # time 1-in-N calls per site; bytes/calls recorded on all

# per-site execution sequence for the sampling decision (GIL-racy by
# design: a lost increment shifts WHICH call gets timed, never whether
# the ledger stays bounded)
_CALL_SEQ: Dict[str, int] = {}


def _conf_int(key: str, default: int) -> int:
    try:
        from auron_tpu.config import conf
        return int(conf.get(key))
    except Exception:  # noqa: BLE001 - config not imported yet
        return default


def _conf_float(key: str, default: float) -> float:
    try:
        from auron_tpu.config import conf
        return float(conf.get(key))
    except Exception:  # noqa: BLE001
        return default


def _conf_bool(key: str, default: bool) -> bool:
    try:
        from auron_tpu.config import conf
        return bool(conf.get(key))
    except Exception:  # noqa: BLE001
        return default


# ---------------------------------------------------------------------------
# bytes estimators
# ---------------------------------------------------------------------------

def _leaf_nbytes(x: Any) -> int:
    """Buffer bytes of one pytree leaf from its aval (shape x itemsize;
    no sync — avals are host metadata)."""
    aval = getattr(x, "aval", None)
    src = aval if aval is not None else x
    shape = getattr(src, "shape", None)
    dtype = getattr(src, "dtype", None)
    if shape is None or dtype is None:
        return 0
    n = 1
    for d in shape:
        n *= int(d)
    return n * int(getattr(dtype, "itemsize", 0) or 0)


def default_estimator(in_leaves: List[Any], out_leaves: List[Any]) -> int:
    """The roofline convention: every input buffer read once + every
    output buffer written once."""
    return (sum(_leaf_nbytes(x) for x in in_leaves) +
            sum(_leaf_nbytes(x) for x in out_leaves))


# (site glob, estimator) in declaration order; first match wins.
# Estimator signature: fn(in_leaves, out_leaves) -> bytes processed.
_ESTIMATORS: List[Tuple[str, Callable[[List[Any], List[Any]], int]]] = []
_ESTIMATOR_CACHE: Dict[str, Callable[[List[Any], List[Any]], int]] = {}

# (site, in-shape key, out-shape key) -> (signature string, nbytes):
# estimators and signatures are pure functions of shapes/dtypes (the
# aval contract), so both are computed once per distinct call shape —
# the armed hot path is a tuple build + one dict hit
_SHAPE_CACHE: Dict[tuple, Tuple[str, int]] = {}
_SHAPE_CACHE_MAX = 4096


def declare_estimator(site_glob: str,
                      fn: Callable[[List[Any], List[Any]], int],
                      ) -> None:
    """Declare the bytes-processed estimator for a kernel family (jit
    sites matching `site_glob`).  Declared next to the kernel it
    describes; undeclared families get `default_estimator`."""
    with _LOCK:
        _ESTIMATORS[:] = [(g, f) for g, f in _ESTIMATORS
                          if g != site_glob]
        _ESTIMATORS.append((site_glob, fn))
        _ESTIMATOR_CACHE.clear()
        _SHAPE_CACHE.clear()   # cached nbytes may come from the old fn


def estimator_for(site: str) -> Callable[[List[Any], List[Any]], int]:
    # unlocked fast path: per-site resolution is memoized (a dict read
    # under the GIL) so the glob scan runs once per site, not per call
    fn = _ESTIMATOR_CACHE.get(site)
    if fn is not None:
        return fn
    with _LOCK:
        fn = default_estimator
        for glob, f in _ESTIMATORS:
            if site == glob or fnmatch.fnmatchcase(site, glob):
                fn = f
                break
        _ESTIMATOR_CACHE[site] = fn
    return fn


def _sort_estimator(in_leaves: List[Any], out_leaves: List[Any]) -> int:
    """Sort-family estimator: a comparator/radix sort streams the key
    buffers more than once — count the keys twice (one read pass + one
    permute pass) plus the index output, the minimal multi-pass form."""
    return (2 * sum(_leaf_nbytes(x) for x in in_leaves) +
            sum(_leaf_nbytes(x) for x in out_leaves))


# sort-shaped families re-stream their key buffers; everything else
# keeps the read-once/write-once default
declare_estimator("agg.sort_base", _sort_estimator)
declare_estimator("spmd.sort*", _sort_estimator)


# ---------------------------------------------------------------------------
# the ledger
# ---------------------------------------------------------------------------

class _SigStats:
    """Per-(site, signature) accounting: bounded sample ring + EMA +
    running totals.  Bytes and call counts are exact (every execution);
    wall time comes from the 1-in-`auron.perf.sample.stride` timed
    calls, so total seconds is the sampled-average x calls estimate."""

    __slots__ = ("calls", "timed_calls", "total_ns", "total_bytes",
                 "ema_ns", "ring")

    def __init__(self) -> None:
        self.calls = 0
        self.timed_calls = 0
        self.total_ns = 0           # raw sum over TIMED calls only
        self.total_bytes = 0
        self.ema_ns = 0.0
        self.ring: List[Tuple[int, int]] = []   # (ns, bytes)

    def add(self, ns: Optional[int], nbytes: int, cap: int,
            alpha: float) -> None:
        self.calls += 1
        self.total_bytes += nbytes
        if ns is None:
            return
        self.timed_calls += 1
        self.total_ns += ns
        self.ema_ns = (float(ns) if self.timed_calls == 1
                       else alpha * ns + (1.0 - alpha) * self.ema_ns)
        if len(self.ring) < cap:
            self.ring.append((ns, nbytes))
        elif cap > 0:
            # deterministic ring replacement (no Date.now/random in the
            # hot path): the reservoir keeps the cap most-recent shape
            self.ring[self.timed_calls % cap] = (ns, nbytes)

    def est_ns(self) -> int:
        """Estimated wall ns across ALL calls (sampled avg x calls)."""
        if not self.timed_calls:
            return 0
        return int(self.total_ns * self.calls / self.timed_calls)

    def to_dict(self) -> Dict[str, Any]:
        return {"calls": self.calls,
                "timed_calls": self.timed_calls,
                "seconds": round(self.est_ns() / 1e9, 6),
                "bytes": self.total_bytes,
                "ema_ms": round(self.ema_ns / 1e6, 4),
                "samples": len(self.ring)}


class SiteLedger:
    """One jit site's performance record, keyed by abstract signature
    (bounded: past `auron.perf.signatures.max` distinct signatures new
    ones collapse into '<other>' — a site re-tracing per shape is
    jitcheck's problem, not a reason for this ledger to grow without
    bound)."""

    __slots__ = ("name", "sigs")

    def __init__(self, name: str) -> None:
        self.name = name
        self.sigs: Dict[str, _SigStats] = {}

    def totals(self) -> Tuple[int, int, int]:
        calls = ns = nbytes = 0
        for s in self.sigs.values():
            calls += s.calls
            ns += s.est_ns()
            nbytes += s.total_bytes
        return calls, ns, nbytes


_SITES: Dict[str, SiteLedger] = {}


def _signature_key(in_leaves: List[Any]) -> str:
    parts = []
    for x in in_leaves[:16]:
        aval = getattr(x, "aval", None)
        src = aval if aval is not None else x
        shape = getattr(src, "shape", None)
        dtype = getattr(src, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append(f"{dtype}[{','.join(str(d) for d in shape)}]")
        elif isinstance(x, (bool, int, float, str)):
            parts.append(repr(x)[:32])
        else:
            parts.append(type(x).__name__)
    return " ".join(parts) or "<none>"


def record(site: str, seconds: Optional[float], nbytes: int,
           signature: str = "<none>") -> None:
    """Record one kernel execution into the ledger (the shim's sink;
    public so tests and the statistics store can feed observations).
    `seconds=None` = an untimed call (bytes + call count only — the
    off-stride executions under sampling)."""
    ns = None if seconds is None else int(seconds * 1e9)
    cap, alpha, max_sigs = _CAP, _ALPHA, _MAX_SIGS
    with _LOCK:
        led = _SITES.get(site)
        if led is None:
            led = _SITES[site] = SiteLedger(site)
        sig = signature
        if sig not in led.sigs and len(led.sigs) >= max_sigs:
            sig = "<other>"
        stats = led.sigs.get(sig)
        if stats is None:
            stats = led.sigs[sig] = _SigStats()
        stats.add(ns, int(nbytes), cap, alpha)


# ---------------------------------------------------------------------------
# the shim (installed by jitcheck.JitSite.jit on every wrapped program)
# ---------------------------------------------------------------------------

# ambient per-operator attribution sink (ops/base.py arms it around each
# batch pull when perfscope is armed): a MetricNode the kernel bytes/ns
# land in, surfacing as the EXPLAIN ANALYZE bytes/GB/s columns
_ATTR: "contextvars.ContextVar[Optional[Any]]" = \
    contextvars.ContextVar("auron_perf_attr", default=None)


class attribution_scope:
    """Bind a MetricNode as the ambient kernel-cost sink (re-entrant:
    the innermost operator pulling batches wins — its compute slice is
    the one the kernels run in)."""

    __slots__ = ("_node", "_token")

    def __init__(self, node: Any) -> None:
        self._node = node

    def __enter__(self) -> "attribution_scope":
        self._token = _ATTR.set(self._node)
        return self

    def __exit__(self, *exc: Any) -> bool:
        _ATTR.reset(self._token)
        return False


def _leaf_key(leaves: List[Any]) -> tuple:
    parts = []
    for x in leaves:
        d = getattr(x, "dtype", None)
        if d is not None:
            parts.append((d, getattr(x, "shape", ())))
        elif isinstance(x, (bool, int, float, str, bytes, type(None))):
            # static scalars: a varying value retraces the jit anyway,
            # so keying on it stays bounded by the retrace count
            parts.append(x)
        else:
            parts.append(type(x).__name__)
    return tuple(parts)


def _record_call(site: str, fn: Callable, args: tuple, kwargs: dict):
    import jax

    # sampling decision up front: blocking after EVERY call serializes
    # dispatch the engine otherwise overlaps with host work (~5% on
    # warm q01) — 1-in-_STRIDE calls pay the block+time, the rest
    # record bytes/calls only
    seq = _CALL_SEQ.get(site, 0)
    _CALL_SEQ[site] = seq + 1
    timed = _STRIDE <= 1 or seq % _STRIDE == 0
    sync = _SYNC and timed
    t0 = time.perf_counter_ns() if timed else 0
    out = fn(*args, **kwargs)
    if sync:
        try:
            jax.block_until_ready(out)
        except Exception:  # noqa: BLE001 - non-blockable leaves (tracers)
            sync = False
    dt_ns = (time.perf_counter_ns() - t0) if timed else None
    try:
        in_leaves = jax.tree_util.tree_leaves((args, kwargs))
        out_leaves = jax.tree_util.tree_leaves(out)
        if any(isinstance(x, jax.core.Tracer) for x in in_leaves):
            # called under an outer trace: timing would be compile time
            # and avals are symbolic — not a ledger observation
            return out
        key = (site, _leaf_key(in_leaves), _leaf_key(out_leaves))
        ent = _SHAPE_CACHE.get(key)
        if ent is None:
            ent = (_signature_key(in_leaves),
                   int(estimator_for(site)(in_leaves, out_leaves)))
            if len(_SHAPE_CACHE) < _SHAPE_CACHE_MAX:
                _SHAPE_CACHE[key] = ent
        sig, nbytes = ent
        record(site, None if dt_ns is None else dt_ns / 1e9, nbytes,
               signature=sig)
        sink = _ATTR.get()
        if sink is not None:
            sink.add("perf_bytes", nbytes)
            if dt_ns is not None:
                # stride-scaled so per-operator kernel ns stays an
                # unbiased estimate of ALL its calls
                sink.add("perf_kernel_ns", dt_ns * max(_STRIDE, 1))
        if dt_ns is not None:
            from auron_tpu.runtime import tracing
            if tracing.current_recorder() is not None:
                tracing.event("kernel.exec", cat="kernel", site=site,
                              nbytes=nbytes, ns=dt_ns,
                              gbps=round(nbytes / max(dt_ns, 1), 3),
                              synced=sync)
    except Exception:  # noqa: BLE001 - accounting must never kill a query
        pass
    return out


def wrap(site: str, fn: Callable) -> Callable:
    """Install the perfscope shim on a site's jitted callable.  Disarmed
    (the default): one module-flag read, then straight through."""
    import functools

    @functools.wraps(fn)
    def timed(*args: Any, **kwargs: Any):
        if not _ARMED:
            return fn(*args, **kwargs)
        return _record_call(site, fn, args, kwargs)

    timed.__perfscope_site__ = site
    return timed


# ---------------------------------------------------------------------------
# machine peak: a published-peaks table on accelerators, a STREAM-style
# memcpy probe of host RAM (verdict cached) on the CPU
# ---------------------------------------------------------------------------

# published HBM bandwidth of one chip in GB/s, keyed by jax's
# `device_kind`.  Source: Google Cloud documentation, "TPU v5e" (16 GB
# of HBM at 819 GB/s); a v5e chip reports itself as "TPU v5 lite".
DEVICE_PEAK_GBPS: Dict[str, float] = {"TPU v5 lite": 819.0}

_PEAK_CACHE: Dict[str, float] = {}   # platform -> GB/s (process cache)
_PEAK_PROBE_BYTES = 1 << 26          # 64 MiB working set


def device_peak_gbps(device_kind: str) -> float:
    """Published peak memory bandwidth of an accelerator.  A device that
    is not in the table is an error, never a default: a roofline share
    against the wrong ceiling is worse than none."""
    try:
        return DEVICE_PEAK_GBPS[device_kind]
    except KeyError:
        raise ValueError(
            f"no published peak for device kind {device_kind!r}: add it "
            f"to perfscope.DEVICE_PEAK_GBPS with its source, or set "
            f"auron.perf.peak.gbps") from None


def _peak_cache_file() -> str:
    try:
        from auron_tpu.config import conf
        raw = str(conf.get("auron.perf.peak.path")).strip()
    except Exception:  # noqa: BLE001
        raw = ""
    if raw:
        return raw
    repo = os.path.dirname(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))))
    return os.path.join(repo, ".jax_cache", "perf_peak.json")


def _platform() -> str:
    try:
        import jax
        return jax.default_backend()
    except Exception:  # noqa: BLE001
        return "unknown"


def measure_peak(reps: int = 5) -> float:
    """STREAM-style copy bandwidth of THIS machine in GB/s: memcpy a
    64MiB buffer `reps` times, best rep wins (2 bytes moved per byte
    copied — read + write, the STREAM 'copy' convention)."""
    import numpy as np
    lockcheck.blocked("perfscope.peak.probe")
    src = np.ones(_PEAK_PROBE_BYTES, dtype=np.uint8)
    dst = np.empty_like(src)
    best = 0.0
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        np.copyto(dst, src)
        dt = time.perf_counter() - t0
        gbps = 2.0 * _PEAK_PROBE_BYTES / max(dt, 1e-9) / 1e9
        if gbps > best:
            best = gbps
    return round(best, 2)


def machine_peak_gbps() -> float:
    """The peak the rooflines divide by: the `auron.perf.peak.gbps`
    override when set; on an accelerator the published peak of its
    `device_kind` (DEVICE_PEAK_GBPS — the host's memcpy speed says
    nothing about HBM); on the CPU the cached probe verdict, else a
    fresh probe whose verdict is cached best-effort."""
    forced = _conf_float("auron.perf.peak.gbps", 0.0)
    if forced > 0:
        return forced
    plat = _platform()
    if plat != "cpu":
        import jax
        return device_peak_gbps(jax.devices()[0].device_kind)
    with _LOCK:
        if plat in _PEAK_CACHE:
            return _PEAK_CACHE[plat]
    path = _peak_cache_file()
    try:
        with open(path) as f:
            ent = json.load(f).get(plat)
        if isinstance(ent, dict) and float(ent.get("gbps", 0)) > 0:
            gbps = float(ent["gbps"])
            with _LOCK:
                _PEAK_CACHE[plat] = gbps
            return gbps
    except (OSError, ValueError):
        pass
    gbps = measure_peak()
    with _LOCK:
        _PEAK_CACHE[plat] = gbps
    try:
        os.makedirs(os.path.dirname(path), exist_ok=True)
        try:
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            doc = {}
        doc[plat] = {"gbps": gbps, "probe_bytes": _PEAK_PROBE_BYTES}
        with open(path, "w") as f:
            json.dump(doc, f)
    except OSError:
        pass  # cache is best-effort; this process keeps its measurement
    return gbps


# ---------------------------------------------------------------------------
# views
# ---------------------------------------------------------------------------

def enabled() -> bool:
    return _ARMED


def configure(enabled: Optional[bool] = None) -> bool:
    """Arm/disarm at runtime.  `None` re-reads `auron.perf.enable`.
    Unlike jitcheck, the shim is installed on every site regardless —
    arming takes effect on the NEXT kernel execution.  The armed-path
    knobs (sync/reservoir/ema/signatures) are snapshotted HERE, not per
    call — changing them under conf.scoped requires re-arming."""
    global _ARMED, _SYNC, _CAP, _ALPHA, _MAX_SIGS
    if enabled is None:
        from auron_tpu.config import conf
        enabled = bool(conf.get("auron.perf.enable"))
    global _STRIDE
    _SYNC = _conf_bool("auron.perf.sync", True)
    _CAP = _conf_int("auron.perf.reservoir.max", 64)
    _ALPHA = _conf_float("auron.perf.ema.alpha", 0.2)
    _MAX_SIGS = _conf_int("auron.perf.signatures.max", 8)
    _STRIDE = max(1, _conf_int("auron.perf.sample.stride", 8))
    _ARMED = bool(enabled)
    return _ARMED


def snapshot() -> Dict[str, Dict[str, Any]]:
    """{site: {calls, seconds, bytes, gbps, signatures: {sig: ...}}} —
    the full ledger view (/rooflines serves `rooflines()`, the compact
    form)."""
    with _LOCK:
        out: Dict[str, Dict[str, Any]] = {}
        for name, led in sorted(_SITES.items()):
            calls, ns, nbytes = led.totals()
            out[name] = {
                "calls": calls,
                "seconds": round(ns / 1e9, 6),
                "bytes": nbytes,
                "gbps": round(nbytes / max(ns, 1), 3),
                "signatures": {s: st.to_dict()
                               for s, st in led.sigs.items()},
            }
        return out


def kernel_seconds() -> Dict[str, float]:
    """{site: total wall seconds} — `auron_kernel_seconds` on /metrics."""
    with _LOCK:
        return {n: round(led.totals()[1] / 1e9, 6)
                for n, led in sorted(_SITES.items())}


def kernel_bytes() -> Dict[str, int]:
    """{site: total estimated bytes} — `auron_kernel_bytes_total`."""
    with _LOCK:
        return {n: led.totals()[2] for n, led in sorted(_SITES.items())}


def rooflines() -> Dict[str, Any]:
    """The per-site roofline table: achieved GB/s vs the machine peak
    (bytes/ns IS GB/s — both are 1e9-scaled)."""
    peak = machine_peak_gbps()
    sites: Dict[str, Any] = {}
    with _LOCK:
        items = [(n, led.totals()) for n, led in sorted(_SITES.items())]
    for name, (calls, ns, nbytes) in items:
        if not calls:
            continue
        gbps = nbytes / max(ns, 1)
        sites[name] = {
            "calls": calls,
            "seconds": round(ns / 1e9, 6),
            "bytes": nbytes,
            "achieved_gbps": round(gbps, 3),
            "gap_ratio": round(peak / max(gbps, 1e-9), 1),
            "pct_of_peak": round(100.0 * gbps / max(peak, 1e-9), 2),
        }
    return {"peak_gbps": peak, "platform": _platform(),
            "armed": _ARMED, "sites": sites}


def render_report(doc: Optional[Dict[str, Any]] = None) -> str:
    """The human face of `rooflines()` (the report CLI and perf_check
    print this): one row per site, achieved vs peak, gap ratio, sample
    counts."""
    doc = doc if doc is not None else rooflines()
    sites = doc.get("sites", {})
    lines = [f"machine peak (STREAM copy): {doc['peak_gbps']:.1f} GB/s "
             f"[{doc.get('platform', '?')}]",
             f"{'site':<28} {'calls':>6} {'bytes':>12} {'seconds':>9} "
             f"{'GB/s':>8} {'peak%':>7} {'gap':>7}"]
    for name in sorted(sites):
        s = sites[name]
        lines.append(
            f"{name:<28} {s['calls']:>6} {s['bytes']:>12} "
            f"{s['seconds']:>9.4f} {s['achieved_gbps']:>8.3f} "
            f"{s['pct_of_peak']:>6.2f}% {s['gap_ratio']:>6.1f}x")
    if not sites:
        lines.append("(no kernel executions recorded — arm with "
                     "auron.perf.enable / AURON_TPU_AURON_PERF_ENABLE)")
    return "\n".join(lines)


def reset_state() -> None:
    """Test hook: drop the ledger (estimator declarations and the peak
    verdict describe the code/machine, not a run — they persist)."""
    with _LOCK:
        _SITES.clear()
        _CALL_SEQ.clear()

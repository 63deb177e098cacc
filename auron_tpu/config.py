"""Typed configuration registry.

Re-designs the reference's config system for a single-process TPU runtime:
Auron has engine-agnostic `ConfigOption<T>` (auron-core/.../ConfigOption.java,
AuronConfiguration.java:26-63) bound to Spark via `SparkAuronConfiguration`
(73 `spark.auron.*` options) and read natively over JNI by reflected static
field name (native-engine/auron-jni-bridge/src/conf.rs:20-63).  Here the
registry is process-local: typed options with defaults, environment-variable
fallback (`AURON_TPU_*`), and programmatic override, readable from both the
Python runtime and (by name) the C++ host runtime.
"""

from __future__ import annotations

import contextvars
import os
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Generic, List, Optional, TypeVar

from auron_tpu.runtime import lockcheck

T = TypeVar("T")


def _env_key(key: str) -> str:
    return "AURON_TPU_" + key.upper().replace(".", "_")


@dataclass(frozen=True)
class ConfigOption(Generic[T]):
    """A typed config option (analogue of auron-core ConfigOption.java)."""

    key: str
    default: T
    type: type
    doc: str = ""
    session_settable: bool = True  # analogue of SQLConfOption

    def parse(self, raw: str) -> T:
        if self.type is bool:
            return raw.strip().lower() in ("1", "true", "yes", "on")  # type: ignore[return-value]
        return self.type(raw)  # type: ignore[call-arg]

    def get(self) -> T:
        """Current value from the registry this option was registered on
        (ConfigOption.java defaultValue/env-fallback resolution)."""
        owner = getattr(self, "_owner", None)
        return (owner if owner is not None else conf).get(self.key)


class Configuration:
    """Mutable view over the registry with env fallback and overrides."""

    def __init__(self) -> None:
        self._options: Dict[str, ConfigOption[Any]] = {}
        self._overrides: Dict[str, Any] = {}
        # reentrant declared: nothing nests it today, but the RLock
        # contract predates lockcheck and option parsers may read other
        # options while an override write holds it
        self._lock = lockcheck.RLock("config", reentrant=True)
        # per-QUERY overlay: a contextvar-held dict consulted before the
        # process-wide overrides, so concurrent queries served out of one
        # process can carry different conf (the serving tier applies each
        # submission's conf map here).  Propagation rides contextvars:
        # task_pool copies the submitting context into worker threads, so
        # a query's tasks see its overlay while other queries' tasks see
        # theirs.  `scoped()` stays process-global (tests and drivers
        # configure the whole engine); `query_scoped()` is the isolated
        # form.
        self._ctx_overlay: contextvars.ContextVar[
            Optional[Dict[str, Any]]] = contextvars.ContextVar(
                "auron_conf_overlay", default=None)

    def register(self, option: ConfigOption[T]) -> ConfigOption[T]:
        with self._lock:
            if option.key in self._options:
                raise ValueError(f"duplicate config option {option.key!r}")
            self._options[option.key] = option
        object.__setattr__(option, "_owner", self)  # frozen dataclass
        return option

    def define(self, key: str, default: T, doc: str = "", **kw: Any) -> ConfigOption[T]:
        return self.register(
            ConfigOption(key=key, default=default, type=type(default), doc=doc, **kw)
        )

    def get(self, key: str) -> Any:
        opt = self._options[key]
        overlay = self._ctx_overlay.get()
        if overlay is not None and key in overlay:
            return overlay[key]
        with self._lock:
            if key in self._overrides:
                return self._overrides[key]
        raw = os.environ.get(_env_key(key))
        if raw is not None:
            return opt.parse(raw)
        return opt.default

    def set(self, key: str, value: Any) -> None:
        opt = self._options[key]
        if not opt.session_settable:
            raise ValueError(f"config option {key!r} is not session-settable")
        if value is not None:
            # strings from a front-end conf map go through the parser so that
            # e.g. "false" disables a bool option instead of bool("false")
            value = opt.parse(value) if isinstance(value, str) and opt.type is not str \
                else opt.type(value)
        with self._lock:
            self._overrides[key] = value

    def unset(self, key: str) -> None:
        with self._lock:
            self._overrides.pop(key, None)

    def options(self) -> List[ConfigOption[Any]]:
        return sorted(self._options.values(), key=lambda o: o.key)

    def generate_doc(self) -> str:
        """Markdown config reference (analogue of
        SparkAuronConfigurationDocGenerator.java)."""
        lines = ["| Key | Type | Default | Description |", "|---|---|---|---|"]
        for o in self.options():
            lines.append(f"| `{o.key}` | {o.type.__name__} | `{o.default!r}` | {o.doc} |")
        return "\n".join(lines)

    class _Scoped:
        def __init__(self, conf: "Configuration", kv: Dict[str, Any]):
            self._conf, self._kv = conf, kv
            self._saved: Dict[str, Any] = {}

        def __enter__(self):
            try:
                for k, v in self._kv.items():
                    with self._conf._lock:
                        self._saved[k] = self._conf._overrides.get(k, _MISSING)
                    self._conf.set(k, v)
            except Exception:
                self.__exit__()  # roll back keys applied before the failure
                raise
            return self._conf

        def __exit__(self, *exc):
            for k, old in self._saved.items():
                with self._conf._lock:
                    if old is _MISSING:
                        self._conf._overrides.pop(k, None)
                    else:
                        self._conf._overrides[k] = old
            return False

    def scoped(self, kv: Optional[Dict[str, Any]] = None,
               **kv_underscored: Any) -> "Configuration._Scoped":
        """Temporarily override options.

        Pass a dict of dotted keys positionally, or kwargs where single `_`
        stands for `.` (option keys themselves never contain underscores):
        `conf.scoped(auron_batch_size=1024)`.
        """
        merged = dict(kv or {})
        merged.update({k.replace("_", "."): v for k, v in kv_underscored.items()})
        return Configuration._Scoped(self, merged)

    class _QueryScoped:
        """Context-local override scope (see _ctx_overlay): visible only
        to the entering context and the contexts copied from it."""

        def __init__(self, conf: "Configuration", kv: Dict[str, Any]):
            self._conf = conf
            # parse against the option types up front so a malformed
            # submission conf fails at scope entry, not mid-query
            parsed: Dict[str, Any] = {}
            for k, v in kv.items():
                opt = conf._options[k]   # KeyError = unknown option
                if v is not None:
                    v = opt.parse(v) if isinstance(v, str) and \
                        opt.type is not str else opt.type(v)
                parsed[k] = v
            self._kv = parsed
            self._token = None

        def __enter__(self) -> "Configuration":
            merged = dict(self._conf._ctx_overlay.get() or {})
            merged.update(self._kv)   # nesting: inner keys win
            self._token = self._conf._ctx_overlay.set(merged)
            return self._conf

        def __exit__(self, *exc) -> bool:
            if self._token is not None:
                self._conf._ctx_overlay.reset(self._token)
            return False

    def query_scoped(self, kv: Optional[Dict[str, Any]] = None
                     ) -> "Configuration._QueryScoped":
        """Temporarily override options for THIS context only (and any
        context copied from it — task_pool worker tasks inherit).  Unlike
        `scoped`, concurrent threads outside the scope keep their own
        view; the serving tier wraps each query's driver in one of these
        so per-query conf (priority, batch sizes, fault specs...) cannot
        bleed between interleaved queries."""
        return Configuration._QueryScoped(self, dict(kv or {}))


_MISSING = object()

conf = Configuration()

# ---------------------------------------------------------------------------
# Redacted keys: options whose VALUES must never leave this process —
# not in dispatch-frame conf overlays, not in worker spawn argv, not in
# /scheduler | /queries JSON, not in trace exports or log prefixes.
# Secrets travel by env fallback (AURON_TPU_*) only; every export
# surface strips them through redact_overlay().
# ---------------------------------------------------------------------------

REDACTED_KEYS = {"auron.net.auth.secret"}


def mark_redacted(key: str) -> None:
    """Register another option key whose value must never be exported."""
    REDACTED_KEYS.add(key)


def redact_overlay(mapping: Optional[Dict[str, Any]],
                   mask: Optional[str] = None) -> Dict[str, Any]:
    """A copy of `mapping` safe for export: redacted keys are DROPPED
    (default — receivers read their own env) or replaced with `mask`
    when a surface needs to show the key existed."""
    out: Dict[str, Any] = {}
    for k, v in (mapping or {}).items():
        if k in REDACTED_KEYS:
            if mask is not None:
                out[k] = mask
            continue
        out[k] = v
    return out


def net_bind_host() -> str:
    """The listen address every server this process starts should bind
    (`auron.net.bind.host`; loopback by default)."""
    return str(conf.get("auron.net.bind.host") or "127.0.0.1")


def net_advertise_host(bind_host: Optional[str] = None) -> str:
    """The host peers should DIAL to reach servers bound on
    `bind_host`: the explicit `auron.net.advertise.host` when set, else
    the bind host itself — except wildcard binds, which are not
    dialable and advertise loopback."""
    adv = str(conf.get("auron.net.advertise.host") or "").strip()
    if adv:
        return adv
    host = bind_host if bind_host is not None else net_bind_host()
    if host in ("", "0.0.0.0", "::", "::0", "0:0:0:0:0:0:0:0"):
        return "127.0.0.1"
    return host

# ---------------------------------------------------------------------------
# Core engine options (names parallel spark.auron.* semantics, TPU-adapted).
# ---------------------------------------------------------------------------

BATCH_SIZE = conf.define(
    "auron.batch.size", 8192, "Target rows per columnar batch fed to jitted kernels."
)
BATCH_CAPACITY_MIN = conf.define(
    "auron.batch.capacity.min", 1024,
    "Smallest padded batch capacity bucket (capacities are powers of two to bound "
    "XLA recompilation).",
)
SUGGESTED_BATCH_MEM_SIZE = conf.define(
    "auron.suggested.batch.mem.size", 8 << 20,
    "Target in-memory bytes per batch (analogue of datafusion-ext-commons "
    "suggested_batch_mem_size, lib.rs:74-100).",
)
SUGGESTED_BATCH_MEM_SIZE_KWAY_MERGE = conf.define(
    "auron.suggested.batch.mem.size.kway.merge", 1 << 20,
    "Smaller batch byte target while k-way merging spills.",
)
MEMORY_FRACTION = conf.define(
    "auron.memory.fraction", 0.6,
    "Fraction of the per-device HBM budget the memory manager hands to consumers.",
)
MEMORY_BUDGET_BYTES = conf.define(
    "auron.memory.budget.bytes", 0,
    "Absolute memory budget override in bytes; 0 = derive from device memory "
    "and auron.memory.fraction.",
)
MEMORY_WATERMARK_FRACTIONS = conf.define(
    "auron.memory.watermark.fractions", "0.5,0.8,0.95",
    "Comma-separated budget fractions the memory manager watches: the "
    "first time pool usage climbs past budget*fraction a watermark "
    "crossing is recorded (memmgr stats, /memory endpoint) and a "
    "mem.pressure trace event is emitted when the query is traced.  "
    "Crossings fire once per fraction per manager lifetime "
    "(reset_manager re-arms).  Empty disables watermark telemetry.",
)
SPILL_COMPRESSION_CODEC = conf.define(
    "auron.spill.compression.codec", "zstd", "Codec for spill files: zstd|zlib|none."
)
SPILL_DIR = conf.define(
    "auron.spill.dir", "", "Directory for spill files ('' = system temp dir)."
)
SHUFFLE_SERVICE = conf.define(
    "auron.shuffle.service", "inprocess",
    "Exchange transport: inprocess | celeborn | uniffle | durable "
    "(remote shuffle service, AuronShuffleManager selection analogue; "
    "`durable` speaks the side-car commit protocol — committed "
    "map-output manifests, stage resume, integrity-checked fetch).")
SHUFFLE_SERVICE_ADDRESS = conf.define(
    "auron.shuffle.service.address", "",
    "host:port of the remote shuffle server for celeborn/uniffle/"
    "durable modes.")
RSS_TAG = conf.define(
    "auron.rss.tag", "",
    "Stable namespace for durable side-car shuffle ids ('' = this "
    "execute's query id).  The fleet sets it to the front-door query "
    "id on every dispatch so a requeued attempt (whose executor-side "
    "query id carries a ~rN suffix) finds the earlier attempt's "
    "committed map outputs and RESUMES instead of recomputing.")
RSS_RESUME_ENABLE = conf.define(
    "auron.rss.resume.enable", True,
    "Consult side-car manifests before running an exchange's map "
    "side: map tasks whose outputs are already committed are skipped "
    "(whole stages when the seal covers every map).  Off forces every "
    "attempt to recompute (the commit protocol still applies).")
RSS_DEFER_CLEANUP = conf.define(
    "auron.rss.defer.cleanup", False,
    "Leave durable side-car blocks in place when a session finishes "
    "(the fleet deletes them by query tag once the submission is "
    "TERMINAL).  Required for resume: a killed attempt cannot clean "
    "up, and a successful one must not delete blocks the fleet still "
    "tracks.  The fleet sets this on every dispatch; standalone "
    "sessions default to cleaning up after themselves.")
RSS_SIDECAR_ENABLE = conf.define(
    "auron.rss.sidecar.enable", False,
    "FleetManager.spawn also launches a shuffle side-car process "
    "(python -m auron_tpu.shuffle_rss.server) that OUTLIVES executors "
    "and routes every worker's exchanges through it "
    "(auron.shuffle.service=durable injected per dispatch).  Executor "
    "death then turns whole-query recompute into partial-stage "
    "resume; side-car death degrades workers back to executor-local "
    "shuffle with a structured diagnostic.")
RSS_SHARDS = conf.define(
    "auron.rss.shards", 1,
    "Durable side-car shard count for FleetManager.spawn: N > 1 runs N "
    "side-car processes with a consistent shuffle-id -> shard map "
    "(shuffle_rss/shard_map.py rendezvous hash over the ordered "
    "address list in auron.shuffle.service.address, so every worker "
    "and the driver agree from the dispatch overlay alone).  Each "
    "shard rides its own health machine: ONE dead shard degrades only "
    "the shuffles it owns; delete_prefix/stats/tspans fan out across "
    "live shards.  1 (default) keeps the single side-car wire "
    "behavior bit-identical.",
)
RSS_COMMITTED_SPILL_WATERMARK = conf.define(
    "auron.rss.committed.spill.watermark", 0,
    "Resident-byte watermark for the side-car's COMMITTED map outputs "
    "(shuffle_rss/server.py): above it, committed blocks spill to "
    "files under the server's spill dir largest-shuffle-first, "
    "manifests keep naming them, and MFETCH restores them "
    "transparently — a side-car survives committed datasets far "
    "beyond RAM.  Spill attribution (committed_spills, "
    "committed_spilled_bytes, committed_restores) rides STATS.  "
    "0 (default) = committed blocks stay resident (the aggregate-"
    "model spill threshold is separate and unchanged).",
)
SHUFFLE_COMPRESSION_CODEC = conf.define(
    "auron.shuffle.compression.codec", "zstd",
    "Codec for shuffle/spill blocks: zstd, zlib, lz4, none."
)
SERDE_FORMAT_VERSION = conf.define(
    "auron.serde.format.version", 2,
    "Exchange wire format written by the shuffle writers: 2 (default) "
    "streams the schema once per (map, partition) stream and frames "
    "the padded DEVICE column layout raw, so the fetch side wraps "
    "received buffers as numpy views and device_puts them with ZERO "
    "per-column decode copies (columnar/serde.py copy_count asserts "
    "it); 1 writes the original per-frame compressed Arrow IPC.  "
    "Readers speak both regardless (frames are self-describing), so "
    "mixed-version streams and spilled v1 runs always decode."
)
SHUFFLE_PIPELINE_DEPTH = conf.define(
    "auron.shuffle.pipeline.depth", 4,
    "Bounded async window for remote-shuffle push AND fetch "
    "(shuffle_rss clients): up to this many pushes ride a per-writer "
    "sender thread while the map task keeps computing, and reduce "
    "fetches for different partitions overlap across this many "
    "connections.  Order per (map, partition) stream is preserved "
    "(one sender, submission order) so push_id dedup, the commit "
    "protocol and reduce-side determinism are untouched; errors "
    "surface at the next push or at flush with their retry "
    "classification intact.  <= 1 restores fully synchronous "
    "push/fetch."
)
SHUFFLE_PID_FUSE = conf.define(
    "auron.shuffle.pid.fuse.enable", True,
    "Splice the exchange's partition-id computation into the "
    "producing FusedFragment's device program as an extra output "
    "column (ops/fused.py `fused.fragment.pid` jit site): the shuffle "
    "writer consumes (batch, pid) from ONE jitted program instead of "
    "dispatching a standalone PartitionIdComputer pass over the "
    "materialized fragment output.  Applies when the writer's child "
    "is a fused fragment and the partitioning keys are device-"
    "capable; host-column batches fall back to the standalone "
    "computer per batch (bit-identical either way)."
)
SHUFFLE_CODEC_LOCAL = conf.define(
    "auron.shuffle.codec.local", "none",
    "Codec for exchange frames pushed through a LOCAL transport (the "
    "in-process shuffle service): the bytes never leave the process, "
    "so compressing them only to decompress in the same address space "
    "burns CPU for nothing — `none` (default) is free bandwidth.  "
    "Empty falls back to auron.shuffle.compression.codec.  Frames are "
    "self-describing, so readers decode any mix."
)
SHUFFLE_CODEC_REMOTE = conf.define(
    "auron.shuffle.codec.remote", "",
    "Codec for exchange frames pushed to a REMOTE shuffle transport "
    "(celeborn / uniffle / durable side-car), where wire bandwidth is "
    "real.  Empty (default) falls back to "
    "auron.shuffle.compression.codec."
)
ADAPTIVE_ENABLE = conf.define(
    "auron.adaptive.enable", False,
    "Adaptive query execution (runtime/adaptive.py): at each stage "
    "boundary of the serial exchange path the driver observes the map "
    "side's REAL per-partition output sizes and re-plans the "
    "not-yet-executed remainder — broadcast-vs-shuffle join "
    "conversion, reduce partition coalescing, skew splitting — with "
    "every rewritten plan re-verified by the static analyzer before "
    "execution and every decision surfaced on SessionResult."
    "aqe_decisions, /queries/<id> and EXPLAIN ANALYZE.  Results are "
    "value-identical with the feature on or off."
)
ADAPTIVE_BROADCAST_ENABLE = conf.define(
    "auron.adaptive.broadcast.enable", True,
    "Allow the broadcast-vs-shuffle join conversion when "
    "auron.adaptive.enable is on."
)
ADAPTIVE_COALESCE_ENABLE = conf.define(
    "auron.adaptive.coalesce.enable", True,
    "Allow reduce partition coalescing when auron.adaptive.enable is "
    "on."
)
ADAPTIVE_SKEW_ENABLE = conf.define(
    "auron.adaptive.skew.enable", True,
    "Allow skew splitting when auron.adaptive.enable is on."
)
ADAPTIVE_BROADCAST_THRESHOLD = conf.define(
    "auron.adaptive.broadcast.threshold.bytes", 1 << 20,
    "Broadcast conversion fires when an exchange's TOTAL observed map "
    "output (wire bytes) lands at or under this and the exchange "
    "feeds the build side of a shuffled hash join with a "
    "conversion-safe join type.  The committed map side is reused — "
    "conversion replaces only the partition-indexed fetch plan with "
    "one collect."
)
ADAPTIVE_TARGET_PARTITION_BYTES = conf.define(
    "auron.adaptive.target.partition.bytes", 1 << 20,
    "Coalescing merges ADJACENT reduce partitions toward this many "
    "observed wire bytes per merged partition (and skew splitting "
    "sizes its fan-out toward it): fewer reduce tasks, fewer jit "
    "signatures.  Co-partitioned exchanges of one stage receive the "
    "same grouping so join key alignment survives."
)
ADAPTIVE_SKEW_FACTOR = conf.define(
    "auron.adaptive.skew.factor", 4.0,
    "A reduce partition is skewed when it holds more than this factor "
    "times the median partition's observed bytes (and more than "
    "auron.adaptive.skew.min.partition.bytes).  The skewed partition "
    "fans out across extra tasks over contiguous block runs with an "
    "order-preserving concat; only row-local consumers qualify."
)
ADAPTIVE_SKEW_MIN_BYTES = conf.define(
    "auron.adaptive.skew.min.partition.bytes", 4 << 20,
    "Skew splitting floor: partitions under this many observed bytes "
    "are never split regardless of the ratio (the fan-out's task "
    "overhead would exceed the imbalance)."
)
ADAPTIVE_FUSE_ADJACENCY = conf.define(
    "auron.adaptive.fuse.adjacency.enable", False,
    "Conversion-side projection/filter adjacency (the PR 3 "
    "follow-up): keep a scan's pushed-down filter ALSO as an explicit "
    "Filter node above the scan when the unified cost model says the "
    "re-evaluation is cheaper than the fusion it unlocks (pushdown "
    "otherwise hides filter/projection chains from the fuser).  "
    "Chosen by cost per SystemML's fusion-plan exemplar, not "
    "greedily; value-identical either way (the scan predicate still "
    "prunes IO)."
)
ADAPTIVE_REFORECAST = conf.define(
    "auron.adaptive.reforecast.enable", True,
    "Release admission reservation at stage boundaries: when adaptive "
    "execution observes an exchange's real size, the scheduler-"
    "registered hook re-forecasts the RUNNING query's reservation "
    "through AdmissionController.reforecast (the same path heartbeat "
    "telemetry feeds), so a query that turns out light lets the "
    "admission queue drain sooner.  Requires "
    "auron.admission.reforecast.enable."
)
TASK_RETRIES = conf.define(
    "auron.task.retries", 0,
    "Per-partition task retry count above the runtime (the Spark "
    "task-retry model the reference inherits; stage inputs are "
    "materialized once, so a retry replays only the failed task). "
    "Only retryable-classified failures (runtime/retry.py: transient "
    "IO, injected device faults) are replayed; deterministic errors "
    "ferry immediately.",
)
FAULTS_SPEC = conf.define(
    "auron.faults.spec", "",
    "Fault-injection spec armed at named fault_point(...) sites "
    "(auron_tpu.faults): ';'-separated 'point:kind[:p=..,seed=..,"
    "max=..,after=..,ms=..,bytes=..,frac=..]' rules, e.g. "
    "'shuffle.push:io:p=0.2,seed=7;spill.write:io:p=0.1'.  Kinds: "
    "io | timeout (retryable), device (retry then degrade to serial), "
    "error (deterministic), latency (sleep ms milliseconds instead of "
    "failing — visible as span durations in a traced run), mem "
    "(reserve bytes — or frac of the budget — out of the memory "
    "manager's effective budget, forcing spill pressure instead of "
    "failing).  Empty (default) = every fault point is a no-op check.",
)
NET_TIMEOUT_SECONDS = conf.define(
    "auron.net.timeout.seconds", 30.0,
    "Socket connect/read timeout for every network client (RSS shuffle "
    "clients, engine-service client, kafka consumer) — replaces the "
    "hard-coded per-client timeouts; <= 0 disables (blocking sockets).",
)
NET_BIND_HOST = conf.define(
    "auron.net.bind.host", "127.0.0.1",
    "Listen address for every framed-TCP server this process starts "
    "(executor endpoint, RSS shuffle side-car, engine service) and the "
    "serving/profiling HTTP port.  The multi-host default stays "
    "loopback; fleet deployments bind '0.0.0.0' (or a NIC address) and "
    "set auron.net.advertise.host to the reachable name peers should "
    "dial.",
)
NET_ADVERTISE_HOST = conf.define(
    "auron.net.advertise.host", "",
    "Host peers should DIAL to reach servers started by this process — "
    "carried in listening lines and hello replies instead of the bind "
    "address (binding 0.0.0.0 is not dialable; binding a NIC address "
    "usually is).  Empty (default): advertise the bind host, or "
    "127.0.0.1 when bound to a wildcard.",
)
NET_AUTH_SECRET = conf.define(
    "auron.net.auth.secret", "",
    "Shared-secret wire authentication for the framed-TCP wires "
    "(rss/executor/engine): when non-empty every client frame carries "
    "a `token` header field (wire protocol >= 1.1) and every server "
    "REFUSES frames whose token is missing or wrong with a structured "
    "deterministic refusal (wire.refusal flight-recorder event, "
    "auron_wire_rejects_total) — the ONE retry policy ferries it "
    "instead of spinning.  Source it from the environment "
    "(AURON_TPU_AURON_NET_AUTH_SECRET): the value is REDACTED from "
    "every export surface (dispatch overlays, worker argv, /scheduler "
    "and /queries JSON, trace exports — config.REDACTED_KEYS) and "
    "workers read their own env copy.  Empty (default) = "
    "unauthenticated wires, frame bytes bit-identical to proto 1.0.",
)
SERVICE_READ_TIMEOUT_SECONDS = conf.define(
    "auron.service.read.timeout.seconds", 300.0,
    "Server-side per-connection read timeout for the engine service and "
    "the standalone shuffle server: a half-dead client that stops "
    "sending mid-conversation is disconnected instead of pinning a "
    "handler thread forever; <= 0 disables.",
)
RETRY_MAX_ATTEMPTS = conf.define(
    "auron.retry.max.attempts", 3,
    "Default attempt budget for the shared retry policy "
    "(runtime/retry.py) used by the network clients and the device "
    "degradation tier; per-task replay uses auron.task.retries instead.",
)
RETRY_BACKOFF_BASE_MS = conf.define(
    "auron.retry.backoff.base.ms", 25.0,
    "First-retry backoff in milliseconds; attempt N sleeps "
    "min(base * 2^(N-1), max) * (1 + jitter * u).",
)
RETRY_BACKOFF_MAX_MS = conf.define(
    "auron.retry.backoff.max.ms", 1000.0,
    "Cap on the exponential retry backoff, in milliseconds.",
)
RETRY_JITTER = conf.define(
    "auron.retry.jitter", 0.25,
    "Jitter fraction added to each backoff; drawn from a seeded RNG "
    "(auron.retry.seed) so schedules are deterministic.",
)
RETRY_SEED = conf.define(
    "auron.retry.seed", 0,
    "Seed for the retry-backoff jitter stream (determinism for tests "
    "and chaos sweeps).",
)
LOG_LEVEL = conf.define(
    "auron.log.level", "INFO",
    "Engine logger level (NATIVE_LOG_LEVEL analogue, conf.rs:63).",
)
IO_COMPRESSION_ZSTD_LEVEL = conf.define(
    "auron.io.compression.zstd.level", 3,
    "zstd level for shuffle/spill frames "
    "(SPARK_IO_COMPRESSION_ZSTD_LEVEL analogue, conf.rs:48).",
)
PARTIAL_AGG_SKIPPING_SKIP_SPILL = conf.define(
    "auron.partial.agg.skipping.skip.spill", True,
    "Allow partial-agg skipping to engage even when spills already "
    "exist; when false, a spilled agg never switches to passthrough "
    "(PARTIAL_AGG_SKIPPING_SKIP_SPILL analogue, conf.rs:42).",
)
INPUT_BATCH_STATISTICS_ENABLE = conf.define(
    "auron.input.batch.statistics.enable", False,
    "Record per-operator input batch/row counts in the metric tree "
    "(INPUT_BATCH_STATISTICS_ENABLE analogue, conf.rs:37).",
)
TASK_PARALLELISM = conf.define(
    "auron.task.parallelism", 0,
    "Thread-pool size for per-partition tasks on the serial fallback "
    "path (one native runtime per task, rt.rs:76-139 analogue). "
    "0 = auto (min(8, cpu count)); 1 = sequential.",
)
SMJ_STREAMING_ENABLE = conf.define(
    "auron.smj.streaming.enable", True,
    "Execute sort-merge joins as a bounded-memory streaming merge of "
    "sorted inputs (window-per-frontier, spillable buffers) instead of "
    "materializing one side (smj/full_join.rs, stream_cursor.rs).",
)
SMJ_FALLBACK_ENABLE = conf.define(
    "auron.smj.fallback.enable", True,
    "Allow broadcast joins to fall back to sort-merge join when the build side "
    "exceeds its memory budget (reference: SMJ_FALLBACK_* conf.rs).",
)
SMJ_FALLBACK_ROWS_THRESHOLD = conf.define(
    "auron.smj.fallback.rows.threshold", 10_000_000,
    "Build-side row threshold beyond which BHJ falls back to SMJ.",
)
SMJ_FALLBACK_MEM_SIZE_THRESHOLD = conf.define(
    "auron.smj.fallback.mem.size.threshold", 1 << 30,
    "Build-side byte threshold beyond which BHJ falls back to SMJ.",
)
AGG_MERGE_FANIN = conf.define(
    "auron.agg.merge.fanin", 8,
    "Staged grouped entries accumulated before one device-side merge "
    "reduce; higher values amortize the per-merge host sync over more "
    "input batches (the multi-level merge analogue, agg_table.rs:323).",
)
SPMD_EXCHANGE_QUOTA_MARGIN = conf.define(
    "auron.spmd.exchange.quota.margin", 2.0,
    "Skew headroom for SPMD hash/round-robin exchanges: each device's "
    "per-destination send quota is ceil(capacity/n_dev) * margin, so "
    "post-exchange buffers are O(global/n_dev * margin) instead of "
    "O(global).  Overflowing rows trip a runtime guard and the driver "
    "falls back to the serial engine.",
)
SPMD_SINGLE_DEVICE = conf.define(
    "auron.spmd.singleDevice.enable", True,
    "Offer plans to the SPMD stage compiler on a 1-device mesh when "
    "the caller passes no mesh: the whole pipeline (exchanges included) "
    "compiles to ONE program instead of per-operator kernels, cutting "
    "compile-bound cold query time ~3x (CPU-measured); plans the stage "
    "compiler rejects still run the serial per-batch path.  Default ON "
    "since round 4 (the stage path IS the engine path, the serial walk "
    "is its fallback — planner.rs:121-130 keeps one native path the "
    "same way); device-resident source caching makes repeat executes "
    "transfer nothing.",
)
SMJ_WINDOW_MAX_ROWS = conf.define(
    "auron.smj.window.max.rows", 1 << 20,
    "Cap on the build rows one streaming-SMJ window may materialize on "
    "device.  A window that exceeds it AND holds a single key (the "
    "degenerate all-ties shape: every row one join key) escapes to a "
    "bounded giant-group join — build chunks spill to storage and the "
    "probe window re-streams per chunk, so resident memory stays "
    "O(cap + one batch) instead of O(group).  Windows with multiple "
    "keys keep the normal path (they are batch-bounded by the frontier "
    "advance).  0 disables the cap.  (The role of the reference's "
    "SMJ_FALLBACK_* knobs, conf.rs.)",
)
SORT_F64_EXACTBITS = conf.define(
    "auron.sort.f64.exactbits", "auto",
    "Exact 64-bit ordering/grouping/hashing for FLOAT64 on backends that "
    "demote f64 (TPU): ingest captures the IEEE bit pattern host-side as "
    "a uint64 sidecar (free: a numpy view), key encoding orders by it, "
    "and device-computed doubles (f32-exact by construction there) widen "
    "losslessly via integer ops — so TPU sort/SMJ/window/group orders "
    "match the oracle bit-for-bit instead of at f32 granularity.  'auto' "
    "= only on demoting backends; 'on' forces the sidecar everywhere "
    "(CPU differential tests); 'off' = legacy f32-granular demotion.",
)
SPMD_AGG_CAPACITY_HINT = conf.define(
    "auron.spmd.agg.capacity.hint", 262144,
    "Static per-device row capacity an SPMD agg output is cut down to "
    "(aggs are the cardinality reducers, but mask-liveness keeps input "
    "capacity — without the cut every downstream exchange/join/sort "
    "pays input-scale cost for a handful of groups).  More groups than "
    "the hint trips a runtime guard and the query climbs a capacity "
    "ladder: 4x the hint per retry up to 16x, then shrink disabled "
    "(the working rung is remembered per program).  It is also the "
    "compaction target of an agg's INPUT: the stage program counts the "
    "live input rows and, per device, brings them to the front of the "
    "narrowest table that holds them — of this capacity, or of a fourth "
    "or a thirty-second of it where that is under 131072 rows (65536 and "
    "8192 at the default) — before it aggregates (they fit after "
    "selective joins), so the agg costs what its live rows cost; more "
    "live rows than this capacity and it aggregates at the input's "
    "capacity and cuts.  0 disables both.",
)
SPMD_JOIN_COMPACT = conf.define(
    "auron.spmd.join.compact.enable", True,
    "Compact K-expanded SPMD join outputs back to the pre-expansion "
    "capacity (stable front-compaction of live rows): a join CHAIN "
    "then stays at the probe capacity instead of growing K-fold per "
    "join (a 5-join chain at K=4 otherwise pays 4^5=1024x row "
    "capacity).  A join whose live output genuinely exceeds the "
    "target trips a runtime guard and the query retries with "
    "compaction off (independent of the agg shrink retry).",
)
SPMD_SOURCE_CACHE_MB = conf.define(
    "auron.spmd.source.cache.mb", 4096,
    "Device-byte budget (MB) for the SPMD source shard cache: sharded + "
    "padded source tables stay device-resident across executes keyed by "
    "(table identity, mesh, string layout), so a repeat execute of the "
    "same query transfers nothing host-to-device (the reference's hot "
    "path does zero per-batch host work, rt.rs:141-238).  0 disables; "
    "LRU eviction past the budget, never of what the query in flight "
    "reads.",
)
SPMD_SCAN_CACHE_MB = conf.define(
    "auron.spmd.scan.cache.mb", 2048,
    "Host-byte budget (MB) for the SPMD materialized-scan cache: scan "
    "leaves are re-read from disk only when a file's (mtime, size) "
    "changes.  0 disables; LRU eviction past the budget, never of what "
    "the query in flight reads.",
)
SPMD_JOIN_MATCH_FACTOR = conf.define(
    "auron.spmd.join.match.factor", 4,
    "Pair-expansion factor the SPMD join retries with after its "
    "single-match guard trips (duplicate build keys): each probe row "
    "may emit up to this many pairs (static output capacity scales by "
    "the factor).  Builds with wider key runs fall back to the serial "
    "engine; <=1 disables the retry.",
)
ORC_SCHEMA_CASE_SENSITIVE = conf.define(
    "auron.orc.schema.case.sensitive", False,
    "Match ORC file columns to the read schema case-sensitively "
    "(ORC_SCHEMA_CASE_SENSITIVE analogue, conf.rs:60; default matches "
    "Spark's case-insensitive resolution).",
)
FFI_INGEST_CACHE_MB = conf.define(
    "auron.ffi.ingest.cache.mb", 1024,
    "Device-byte budget (MB) for the FFI-reader ingest cache: decoded "
    "device batches are cached per source RecordBatch identity (weak "
    "keys, FIFO eviction), so repeated executes over one materialized "
    "source re-upload nothing — the serial-path sibling of "
    "auron.spmd.source.cache.mb.  0 disables.",
)
PARTIAL_AGG_SKIPPING_ENABLE = conf.define(
    "auron.partial.agg.skipping.enable", True,
    "Skip partial aggregation when cardinality reduction is poor "
    "(reference: agg_ctx.rs:63-66).",
)
PARTIAL_AGG_SKIPPING_RATIO = conf.define(
    "auron.partial.agg.skipping.ratio", 0.999,
    "Unique-groups/rows ratio above which partial agg passes rows through.",
)
PARTIAL_AGG_SKIPPING_MIN_ROWS = conf.define(
    "auron.partial.agg.skipping.min.rows", 20480,
    "Do not consider partial-agg skipping before this many input rows.",
)
PARQUET_ENABLE_PAGE_FILTERING = conf.define(
    "auron.parquet.enable.page.filtering", True,
    "Apply predicate pushdown (row-group/page pruning) in the Parquet scan.",
)
PARQUET_ENABLE_BLOOM_FILTER = conf.define(
    "auron.parquet.enable.bloom.filter", True,
    "Use Parquet bloom filters when pruning row groups.",
)
IGNORE_CORRUPTED_FILES = conf.define(
    "auron.ignore.corrupted.files", False,
    "Tolerate unreadable input splits (reference conf.rs:38).",
)
UDF_FALLBACK_ENABLE = conf.define(
    "auron.udf.fallback.enable", True,
    "Evaluate unconvertible expressions via the host-python UDF wrapper "
    "(analogue of SparkUDFWrapperExpr).",
)
TOKIO_WORKER_THREADS_PER_CPU = conf.define(
    "auron.host.io.threads", 4,
    "Host IO/prefetch thread count (reference rt.rs:107-111 sizes a per-task "
    "tokio pool; here it sizes the native host thread pool).",
)
CASE_SENSITIVE = conf.define(
    "auron.case.sensitive", False, "Case sensitivity for column resolution."
)
ENABLE_METRICS = conf.define("auron.metrics.enable", True, "Collect operator metrics.")
FORCE_SHUFFLED_HASH_JOIN = conf.define(
    "auron.force.shuffled.hash.join", False,
    "Prefer shuffled-hash-join over sort-merge-join when both are legal "
    "(reference: ForceApplyShuffledHashJoinInjector).",
)
ON_HEAP_SPILL = conf.define(
    "auron.spill.host.memory.first", True,
    "Spill device memory to pinned host RAM before falling back to files "
    "(analogue of OnHeapSpill vs FileSpill, auron-memmgr/src/spill.rs).",
)
NATIVE_LIB_ENABLE = conf.define(
    "auron.native.enable", True,
    "Use the C++ host runtime (libauron_host.so) when built; pure-python "
    "fallbacks are used otherwise.",
)
STRING_WIDTH_BUCKETS = conf.define(
    "auron.string.width.buckets", "8,16,32,64,128,256",
    "Fixed string byte-widths used for device string columns.",
)
ASCII_CASE_KERNELS = conf.define(
    "auron.string.ascii.case.enable", False,
    "Run upper/lower/initcap as device ASCII kernels (fast but byte-level: "
    "non-ASCII characters keep their case).  Off = exact unicode semantics "
    "on the host path.",
)
DEVICE_STRING_MAX_WIDTH = conf.define(
    "auron.string.device.max.width", 256,
    "Strings longer than this stay host-resident (hybrid execution).",
)

# per-operator enable switches (reference: SparkAuronConfiguration:312-496)
for _op in (
    "project", "filter", "sort", "agg", "limit", "union", "expand", "window",
    "generate", "parquet.scan", "orc.scan", "parquet.sink", "orc.sink",
    "shuffle", "smj", "shj", "bhj", "ffi.reader", "coalesce.batches",
    "rename.columns", "empty.partitions", "debug", "kafka.scan",
):
    conf.define(f"auron.enable.{_op}", True, f"Enable native {_op} operator.")

ENABLE = conf.define(
    "auron.enable", True,
    "Master switch: when false the front-end session leaves foreign plans "
    "untouched (reference: spark.auron.enable).",
)
DECIMAL_ARITH_ENABLE = conf.define(
    "auron.decimal.arith.enable", True,
    "Convert +,-,*,/ over decimals natively (reference "
    "decimalArithOpEnabled gating, NativeConverters.scala:579-755).",
)
CASE_CONVERT_FUNCTIONS_ENABLE = conf.define(
    "auron.caseconvert.functions.enable", True,
    "Convert lower()/upper() natively (reference "
    "CASE_CONVERT_FUNCTIONS_ENABLE; locale-divergence escape hatch).",
)
DATETIME_EXTRACT_ENABLE = conf.define(
    "auron.datetime.extract.enable", True,
    "Convert hour()/minute()/second() natively (reference "
    "datetimeExtractEnabled, NativeConverters.scala:980-986).",
)

SPILL_MIN_TRIGGER = conf.define(
    "auron.memory.spill.min.trigger.bytes", 16 << 20,
    "Consumers below this size are never forced to spill "
    "(reference MIN_TRIGGER_SIZE, auron-memmgr/src/lib.rs:36).",
)
FUSE_ENABLE = conf.define(
    "auron.fuse.enable", True,
    "Pipeline-fragment fusion (runtime/fusion.py): lower maximal chains "
    "of row-local operators (projection, filter, coalesce_batches, "
    "limit, expand, rename_columns) into single FusedFragment operators "
    "whose device stages compile to ONE jitted program per fragment.  "
    "Off restores the unfused per-operator planner output (bisection "
    "switch).",
)
COMPILE_CACHE_DIR = conf.define(
    "auron.compile.cache.dir", "auto",
    "Persistent XLA compilation-cache directory "
    "(jax_compilation_cache_dir).  Where the JAX_COMPILATION_CACHE_DIR "
    "environment variable is set, JAX's own handling of it stands and "
    "this option sets no directory.  Otherwise 'auto' = the fixed "
    "<repo>/.jax_cache on non-CPU backends only (CPU compiles thousands "
    "of tiny programs fast, and this jaxlib's CPU AOT serialization is "
    "unsound — see tests/conftest.py); 'off' or '' sets nothing; any "
    "other value is an explicit cache path applied on every backend.",
)
PLAN_VERIFY = conf.define(
    "auron.plan.verify", False,
    "Run the static plan verifier (auron_tpu.analysis: schema check, "
    "column resolution, partitioning contracts, TPU lints, serde "
    "round-trip) over every TaskDefinition before building its operator "
    "tree; error diagnostics abort the task with the offending node "
    "paths logged through runtime/task_logging.  Off by default in "
    "production (the front-end is trusted); forced on under the test "
    "suite (tests/conftest.py).",
)
TRACE_ENABLE = conf.define(
    "auron.trace.enable", False,
    "Record a query-lifecycle trace per AuronSession.execute "
    "(runtime/tracing.py): spans for plan conversion, analyzer verify, "
    "fusion rewrite, SPMD stage compile/launch, per-(stage, partition) "
    "task execution, shuffle push/fetch, spill write/read, "
    "engine-service calls and retry/fallback events, exported as "
    "Chrome-trace JSON on SessionResult.trace (validate/summarize with "
    "`python -m auron_tpu.trace`).  Off (default) costs one contextvar "
    "read per span site on the hot path.",
)
TRACE_MAX_EVENTS = conf.define(
    "auron.trace.max.events", 100_000,
    "Per-query span buffer bound (runtime/tracing.py): events past the "
    "cap are counted as dropped instead of growing the recorder without "
    "bound (a megarow scan with per-operator events stays O(cap)).",
)
TRACE_STITCH_ENABLE = conf.define(
    "auron.trace.stitch.enable", True,
    "Fleet trace stitching (serving/fleet.py + runtime/tracing.py): "
    "with tracing on, the driver harvests span increments from worker "
    "processes over heartbeats and from the RSS side-car at terminal "
    "states, aligns them with heartbeat RTT-midpoint clock offsets, "
    "and records ONE per-query Chrome trace with per-process lanes on "
    "its own /queries history.  Off keeps tracing process-local (each "
    "process still records and exports its own spans).",
)
EVENTS_MAX = conf.define(
    "auron.events.max", 512,
    "Fleet flight-recorder ring size (runtime/events.py): structured "
    "causal events — executor death, kill-and-requeue, side-car "
    "degrade, preemption, scale up/down, circuit-break, shed — kept "
    "for GET /events; the oldest events fall off past the bound.",
)
METRICS_HISTORY_MAX = conf.define(
    "auron.metrics.history.max", 64,
    "Completed-query history ring size (runtime/tracing.py): records "
    "feed the profiling server's /queries page and the cross-query "
    "aggregates on the Prometheus /metrics view.",
)
PROFILING_HTTP_ENABLE = conf.define(
    "auron.profiling.http.enable", False,
    "Lazily start the HTTP profiling service on first task execution "
    "(reference feature http-service, exec.rs:53-59): /debug/profile "
    "(jax trace zip), /debug/pyspy (folded stacks), /metrics, /status.",
)
SPILL_VICTIM_STRATEGY = conf.define(
    "auron.memory.spill.victim.strategy", "rate",
    "How the memory manager ranks spill victims during arbitration: "
    "'rate' prefers the consumer with the best observed freed-bytes-per-"
    "wall-second from the spill attribution history (consumers with no "
    "history rank by current size, i.e. fall back to largest-consumer, "
    "and are tried first so they earn a history entry); 'largest' "
    "restores the pure largest-consumer policy (lib.rs:303-423); "
    "'query' prefers the consumer belonging to the most-over-budget "
    "QUERY in the per-query ledger (auron.memory.query.budget.bytes) — "
    "the overload-survival policy that charges pressure to the query "
    "causing it instead of the globally best-rate consumer.",
)
MEMORY_QUERY_BUDGET_BYTES = conf.define(
    "auron.memory.query.budget.bytes", 0,
    "Per-QUERY memory budget enforced inside the MemManager "
    "(memmgr/manager.py): consumers carry the query tag of the ambient "
    "query id, usage is ledgered per query, and a query over this "
    "budget has one of its own consumers spilled even while the shared "
    "pool is under budget.  0 disables per-query enforcement (the "
    "ledger is still maintained for /memory and the preemption "
    "victim ranking).",
)
MEMORY_QUERY_KILL_GRACE_SPILLS = conf.define(
    "auron.memory.query.kill.grace.spills", 3,
    "Grace allowance before the memory manager KILLS an over-budget "
    "query: a query still over auron.memory.query.budget.bytes after "
    "this many of its spills is preempted through the task pool's "
    "cancel fast-fail path (task_pool.preempt_query — the serving "
    "scheduler requeues it; without a scheduler the query fails with "
    "QueryCancelled).  <= 0 disables manager-initiated kills.",
)
QUERY_PRIORITY = conf.define(
    "auron.query.priority", 1,
    "Fair-share weight of a query's tasks in the shared task pool "
    "(runtime/task_pool.py): per-query queues are drained weighted "
    "round-robin, a weight-N query receiving N task slots per cycle.  "
    "Set per query via the serving submission conf (or conf."
    "query_scoped); clamped to [1, 64].",
)
SERVING_MAX_CONCURRENT = conf.define(
    "auron.serving.max.concurrent", 4,
    "Maximum queries the QueryScheduler (auron_tpu.serving) drives "
    "concurrently; admitted submissions beyond it wait in the admission "
    "queue.  Each running query gets its own driver thread and session; "
    "their tasks share the fair-share task pool.",
)
SERVING_RESULT_MAX_ROWS = conf.define(
    "auron.serving.result.max.rows", 65536,
    "Row cap on the /result/<id> HTTP payload (JSON rows); larger "
    "results are truncated with a 'truncated' marker in the response.  "
    "The Arrow result stream (?format=arrow) is NOT capped — large "
    "results flow to clients as chunked Arrow IPC frames.",
)
SERVING_RESULT_FORMAT = conf.define(
    "auron.serving.result.format", "json",
    "Default GET /result/<id> representation when the request names "
    "none: 'json' (row-capped rows) or 'arrow' (chunked Arrow IPC "
    "stream).  A request's ?format= query arg or an Accept: "
    "application/vnd.apache.arrow.stream header overrides it per "
    "call.",
)
SERVING_RESULT_STREAM_ENABLE = conf.define(
    "auron.serving.result.stream.enable", True,
    "Publish result partitions into the per-query result stream "
    "(runtime/result_stream.py) AS TASKS COMPLETE, so GET "
    "/result/<id>?format=arrow&since=N serves incremental Arrow IPC "
    "frames for a RUNNING query (the PR 13 ack-cursor drain shape).  "
    "Off: results are only available whole, after the query "
    "succeeds.",
)
SERVING_RESULT_STREAM_MAX_MB = conf.define(
    "auron.serving.result.stream.max.mb", 64,
    "Byte budget for buffered, not-yet-drained result-stream frames "
    "per query; past it new frames are dropped from the stream with a "
    "'truncated' flag (the terminal ?format=arrow fetch still serves "
    "the FULL stored table).",
)
ADMISSION_ENABLE = conf.define(
    "auron.admission.enable", True,
    "Gate query START on forecast memory peaks (auron_tpu.serving."
    "admission): an admitted query's forecast is reserved out of the "
    "MemManager budget (add_reservation) until it completes, and "
    "submissions that do not fit wait in the admission queue (or are "
    "shed / degraded to serial per the other auron.admission.* knobs).  "
    "Off = every submission starts as soon as a driver slot is free.",
)
ADMISSION_DEFAULT_FORECAST_BYTES = conf.define(
    "auron.admission.default.forecast.bytes", 64 << 20,
    "Memory-peak forecast for a plan signature with no recorded "
    "history (auron_tpu.serving.forecast).  Once a signature completes "
    "a run, the observed per-operator mem_peak history replaces this.",
)
ADMISSION_FORECAST_MARGIN = conf.define(
    "auron.admission.forecast.margin", 1.2,
    "Multiplier applied to the recorded mem_peak history when "
    "forecasting a submission's reservation (headroom for data growth "
    "between runs of one plan signature).",
)
ADMISSION_MEMORY_FRACTION = conf.define(
    "auron.admission.memory.fraction", 0.8,
    "Fraction of the MemManager budget the admission controller may "
    "promise to concurrently-running queries (sum of forecasts); a "
    "submission pushing the ledger past it queues until a running "
    "query releases its reservation.",
)
ADMISSION_QUEUE_MAX = conf.define(
    "auron.admission.queue.max", 64,
    "Admission queue length past which new submissions are SHED "
    "(rejected with HTTP 429) instead of queued — bounded overload "
    "behavior, the Sparkle-style arbitration backstop.",
)
ADMISSION_QUEUE_TIMEOUT_SECONDS = conf.define(
    "auron.admission.queue.timeout.seconds", 300.0,
    "A submission queued longer than this fails with an admission "
    "timeout instead of waiting forever; <= 0 disables.",
)
ADMISSION_DEGRADE_SERIAL_FRACTION = conf.define(
    "auron.admission.degrade.serial.fraction", 0.5,
    "Forecasts above this fraction of the MemManager budget degrade "
    "the query to SERIAL execution (task parallelism 1, no SPMD stage "
    "program) so its concurrent-partition memory footprint shrinks "
    "instead of being shed; 0 disables degradation.",
)
ADMISSION_REFORECAST_ENABLE = conf.define(
    "auron.admission.reforecast.enable", True,
    "Let the fleet re-forecast a RUNNING query's admission "
    "reservation from live heartbeat memory telemetry instead of only "
    "learning at completion: a query observed well under its forecast "
    "releases the difference early (queue drains sooner), one over it "
    "grows its reservation (neighbors stop over-admitting).  Shrinks "
    "are gated on auron.admission.reforecast.min.age.seconds.",
)
ADMISSION_REFORECAST_MIN_AGE_SECONDS = conf.define(
    "auron.admission.reforecast.min.age.seconds", 5.0,
    "A running query younger than this never has its reservation "
    "SHRUNK by a live re-forecast (its peak may simply not have "
    "happened yet); growth applies immediately.",
)
ADMISSION_AGING_SECONDS = conf.define(
    "auron.admission.aging.seconds", 30.0,
    "Priority aging interval for queued submissions (serving/"
    "scheduler.py): every full interval a submission has waited in the "
    "admission queue bumps its EFFECTIVE priority by one (clamped to "
    "64), so requeued and long-queued submissions cannot starve behind "
    "a stream of high-priority arrivals.  The submission's declared "
    "priority (fair-share task weight) is unchanged; <= 0 disables "
    "aging.",
)
SERVING_PREEMPT_WATERMARK = conf.define(
    "auron.serving.preempt.watermark", 0.95,
    "Pool-usage fraction of the effective MemManager budget past which "
    "the QueryScheduler preempts a running victim (lowest effective "
    "priority, most over forecast): the victim is cancelled through "
    "the task pool's fast-fail path, its reservation released, and the "
    "submission requeued with its original conf overlay — re-execution "
    "is bit-identical to a solo run.  Requires >= 2 running queries "
    "(preempting the only query cannot relieve pressure); <= 0 "
    "disables preemption.",
)
SERVING_PREEMPT_MAX_PER_QUERY = conf.define(
    "auron.serving.preempt.max.per.query", 2,
    "Preemption cap per submission: a query preempted this many times "
    "is no longer selected as a pressure victim, and a manager-"
    "initiated kill past the cap FAILS the query instead of requeueing "
    "forever — guaranteed forward progress under sustained overload.",
)
SERVING_PREEMPT_COOLDOWN_SECONDS = conf.define(
    "auron.serving.preempt.cooldown.seconds", 2.0,
    "Minimum seconds between scheduler-initiated preemptions: memory "
    "pressure is re-evaluated on every accounting update, so the "
    "cooldown keeps one crossing from cascading into a preemption "
    "storm before the first victim's memory is even released.",
)

# -- executor fleet (auron_tpu/serving/fleet.py) ----------------------------

FLEET_EXECUTORS = conf.define(
    "auron.fleet.executors", 0,
    "Executor-process count for fleet serving (`python -m "
    "auron_tpu.serving` / serving.fleet.FleetManager.spawn): N > 0 "
    "spawns N worker processes each running a slim executor server "
    "(serving/executor_endpoint.py) behind ONE front-door "
    "admission ledger, with heartbeat-driven failover and "
    "cross-process kill-and-requeue.  0 (default) keeps the "
    "single-process QueryScheduler path — the fleet code stays "
    "dormant.",
)
FLEET_HEARTBEAT_SECONDS = conf.define(
    "auron.fleet.heartbeat.seconds", 2.0,
    "Heartbeat probe cadence per executor while it is healthy "
    "(serving/fleet.py).  A SUSPECT executor is re-probed faster — "
    "capped exponential backoff starting at a quarter of this "
    "interval (see auron.fleet.probe.backoff.max.seconds) — so a "
    "dead executor is declared within ~auron.fleet.death.probes "
    "heartbeat intervals.  The heartbeat reply also carries the "
    "executor's in-flight query states, so result latency in fleet "
    "mode is bounded by this interval too.",
)
FLEET_DEATH_PROBES = conf.define(
    "auron.fleet.death.probes", 3,
    "Consecutive failed heartbeat probes before an executor is "
    "declared DEAD: its in-flight queries are requeued on a "
    "DIFFERENT executor (per-query excluded-executor list, admission "
    "reservation released first, no `auron.task.retries` budget "
    "consumed) and its process is killed as a fence against double "
    "execution.  DEAD is sticky — a restarted executor joins as a "
    "fresh endpoint, it never resurrects the old identity.",
)
FLEET_PROBE_BACKOFF_MAX_SECONDS = conf.define(
    "auron.fleet.probe.backoff.max.seconds", 0.0,
    "Cap on the suspect re-probe backoff (base = heartbeat/4, doubled "
    "per consecutive failure).  0 (default) caps at "
    "auron.fleet.heartbeat.seconds, keeping worst-case death "
    "detection within ~3 heartbeat intervals.",
)
FLEET_FLAP_MAX = conf.define(
    "auron.fleet.flap.max", 3,
    "Alive->suspect transitions within auron.fleet.flap.window."
    "seconds past which a FLAPPING executor is circuit-broken out of "
    "routing for auron.fleet.circuit.break.seconds: it keeps its "
    "running queries and keeps answering heartbeats, but receives no "
    "new dispatches until the breaker closes.",
)
FLEET_FLAP_WINDOW_SECONDS = conf.define(
    "auron.fleet.flap.window.seconds", 60.0,
    "Sliding window over which alive->suspect transitions count "
    "toward the flap circuit-breaker (auron.fleet.flap.max).",
)
FLEET_CIRCUIT_BREAK_SECONDS = conf.define(
    "auron.fleet.circuit.break.seconds", 30.0,
    "How long a flapping executor stays out of routing once its "
    "circuit-breaker opens.",
)
FLEET_MEMORY_BUDGET_BYTES = conf.define(
    "auron.fleet.memory.budget.bytes", 0,
    "Global memory budget federated across the executor fleet: each "
    "spawned worker process gets an equal slice as its own MemManager "
    "budget, and the front-door admission ledger gates against the "
    "TOTAL.  0 (default) federates the driver process's MemManager "
    "budget instead.",
)
FLEET_BOOT_TIMEOUT_SECONDS = conf.define(
    "auron.fleet.boot.timeout.seconds", 120.0,
    "How long FleetManager.spawn waits for a worker process to print "
    "its listening line before declaring the boot failed (the worker "
    "is killed and its log tail surfaced in the error).",
)
FLEET_LAUNCHER = conf.define(
    "auron.fleet.launcher", "local",
    "How FleetManager.spawn starts worker and side-car processes "
    "(serving/fleet.py WorkerLauncher seam): 'local' (default) forks "
    "children on this host exactly as before; 'command' wraps every "
    "spawn in the argv template from auron.fleet.launcher.command — "
    "the ssh/k8s-shaped remote hook.  Either way the child prints the "
    "same listening-line JSON and ADVERTISES a reachable host:port "
    "(auron.net.advertise.host) instead of the driver assuming "
    "loopback.",
)
FLEET_LAUNCHER_COMMAND = conf.define(
    "auron.fleet.launcher.command", "",
    "Whitespace-split argv template for auron.fleet.launcher=command.  "
    "The token '{argv}' expands in place to the worker's own argv "
    "(python -m auron_tpu.serving.executor_endpoint ... or the "
    "side-car module); '{python}' expands to this driver's "
    "interpreter.  Example: 'ssh worker-2 -- {argv}' or a container "
    "wrapper script.  The launched command must still print the "
    "worker's listening-line JSON on stdout.  Empty with "
    "launcher=command is a spawn-time error.",
)
FLEET_SCALE_UP_QUEUE_DEPTH = conf.define(
    "auron.fleet.scale.up.queue.depth", 0,
    "Elastic fleet sizing, scale-up half: when the fleet queue depth "
    "exceeds this, the monitor spawns one more worker (bounded by "
    "auron.fleet.scale.max.workers and the scale cooldown).  0 "
    "(default) disables scale-up.  Only active when the fleet knows "
    "how to build workers (FleetManager.spawn / a worker_factory).",
)
FLEET_SCALE_IDLE_SECONDS = conf.define(
    "auron.fleet.scale.idle.seconds", 0.0,
    "Elastic fleet sizing, scale-down half: a worker with no in-flight "
    "work for this long is retired through the decommission drain "
    "(queued work rerouted, then the endpoint closed), bounded below "
    "by auron.fleet.scale.min.workers.  0 (default) disables "
    "scale-down.",
)
FLEET_SCALE_MIN_WORKERS = conf.define(
    "auron.fleet.scale.min.workers", 1,
    "Idle retirement never shrinks the fleet below this many live "
    "workers.",
)
FLEET_SCALE_MAX_WORKERS = conf.define(
    "auron.fleet.scale.max.workers", 8,
    "Queue-depth scale-up never grows the fleet beyond this many live "
    "workers.",
)
FLEET_SCALE_COOLDOWN_SECONDS = conf.define(
    "auron.fleet.scale.cooldown.seconds", 5.0,
    "Minimum spacing between elastic scaling actions (up or down) so "
    "a bursty queue cannot spawn a worker storm.",
)

LOCKCHECK_ENABLE = conf.define(
    "auron.lockcheck.enable", False,
    "Dynamic concurrency checking (runtime/lockcheck.py): every lock "
    "created through the named-lock registry tracks a per-thread "
    "held-lock stack and a process-wide acquisition-order graph, "
    "diagnosing lock-order cycles (potential deadlocks) at acquire "
    "time, undeclared re-entrant acquisition, and blocking surfaces "
    "(fault points, retry backoff sleeps, spill IO, socket calls, "
    "condition waits) reached while a lock is held.  Decided at lock "
    "CONSTRUCTION: set the env fallback (AURON_TPU_AURON_LOCKCHECK_"
    "ENABLE=1) at process start; off (default) the factories return "
    "raw threading primitives — zero added cost.  Forced on under the "
    "test suite (tests/conftest.py), like auron.plan.verify.",
)
LOCKCHECK_RAISE = conf.define(
    "auron.lockcheck.raise", True,
    "Raise LockcheckError at the violating acquire/blocking site "
    "(keeps program state consistent: the diagnostic fires BEFORE the "
    "acquisition proceeds).  Off = record structured diagnostics "
    "(lockcheck.diagnostics()) without raising.",
)
JITCHECK_ENABLE = conf.define(
    "auron.jitcheck.enable", False,
    "Compilation-hygiene checking (runtime/jitcheck.py): every jitted "
    "program constructed through the named jit-site registry carries a "
    "trace probe that counts compiles per (site, abstract signature), "
    "diagnosing retrace storms (one program re-traced past "
    "auron.jitcheck.retrace.max distinct signatures) and, with the "
    "transfer guard, undeclared implicit device->host transfers inside "
    "hot execution regions.  Decided when a site WRAPS a program: set "
    "the env fallback (AURON_TPU_AURON_JITCHECK_ENABLE=1) at process "
    "start; off (default) the sites return raw jax.jit products — "
    "zero added cost.  Forced on under the test suite "
    "(tests/conftest.py), like auron.lockcheck.enable.",
)
JITCHECK_RAISE = conf.define(
    "auron.jitcheck.raise", True,
    "Raise JitcheckError at the violating trace/transfer site.  Off = "
    "record structured diagnostics (jitcheck.diagnostics()) without "
    "raising.",
)
JITCHECK_RETRACE_MAX = conf.define(
    "auron.jitcheck.retrace.max", 8,
    "Distinct abstract signatures ONE program at a jit site may "
    "accumulate before the retrace-storm diagnostic fires (the shape-"
    "polymorphic-cache-key bug class; the diagnostic includes the "
    "signature diff between the last two traces).  <= 0 disables the "
    "storm check (compile counting stays on).",
)
JITCHECK_TRANSFER_GUARD = conf.define(
    "auron.jitcheck.transfer.guard", True,
    "With jitcheck enabled, wrap task execution and SPMD stage "
    "execution in jax.transfer_guard_device_to_host('disallow'): "
    "implicit device->host transfers (np.asarray on a device array, "
    "float() on a device scalar) raise as undeclared-transfer "
    "diagnostics.  Deliberate syncs route through "
    "kernel_cache.host_sync or jitcheck.declared_transfer(site) with "
    "a '# jitcheck: waive' comment.",
)
WIRECHECK_ENABLE = conf.define(
    "auron.wirecheck.enable", False,
    "Wire-protocol conformance checking (runtime/wirecheck.py): frame "
    "headers on the framed-TCP wires (executor endpoint, RSS shuffle "
    "server, engine service) are validated against the declarative "
    "command registry at the client send/receive boundaries (structured "
    "WirecheckError with wire, command, field and fix hint instead of a "
    "downstream KeyError) and at the server receive boundary (answered "
    "in-band as a deterministic error; the connection survives).  "
    "Decided at process start from the env fallback (AURON_TPU_AURON_"
    "WIRECHECK_ENABLE=1); off (default) every check is one flag read "
    "and the framed path is bit-identical to the unchecked one.  "
    "Forced on under the test suite (tests/conftest.py), like "
    "auron.lockcheck.enable.  The static half is `python -m "
    "auron_tpu.analysis --protocol` against tests/golden_plans/"
    "wire_manifest.txt.",
)
WIRECHECK_RAISE = conf.define(
    "auron.wirecheck.raise", True,
    "Raise WirecheckError at the violating client send/receive site "
    "(the malformed frame never crosses the wire).  Off = record "
    "structured diagnostics (wirecheck.diagnostics()) without raising.  "
    "Server-side validation never raises either way: it answers "
    "in-band.",
)
WIRE_PROTO_VERSION = conf.define(
    "auron.wire.proto.version", "",
    "Override the protocol version this process ADVERTISES (hello "
    "responses, listening lines) and asserts as a client — a test "
    "hook for impersonating a newer peer in version-handshake tests.  "
    "Empty (default) = the build's own version (wirecheck.PROTO_MAJOR."
    "PROTO_MINOR).  Peers refuse a newer MAJOR version with a "
    "structured refusal frame; minor drift is compatible by the "
    "fix-forward rule.",
)
PERF_ENABLE = conf.define(
    "auron.perf.enable", False,
    "Arm perfscope: every jitcheck-registered jit site records wall "
    "seconds + estimated bytes per (site, signature) into bounded "
    "reservoirs, feeding EXPLAIN ANALYZE bytes/GB/s columns, GET "
    "/rooflines, auron_kernel_seconds / auron_kernel_bytes_total "
    "Prometheus series, and `python -m auron_tpu.perfscope report`.  "
    "Off (default) = one module-flag read per kernel call, ledgers "
    "stay empty, results bit-identical.",
)
PERF_SYNC = conf.define(
    "auron.perf.sync", True,
    "With perfscope armed, block_until_ready() each timed kernel's "
    "outputs so recorded wall time is device time, not dispatch time.  "
    "Off = time the (async) dispatch only — cheaper, but on real "
    "accelerators the numbers become lower bounds.",
)
PERF_SAMPLE_STRIDE = conf.define(
    "auron.perf.sample.stride", 8,
    "With perfscope armed, time (and under auron.perf.sync, block on) "
    "every Nth kernel execution per site; the other calls record bytes "
    "and call counts only.  Blocking each call serializes dispatch the "
    "engine otherwise overlaps with host work (~5% on warm q01), so "
    "sampling is how the armed mode stays inside the perf_check.sh "
    "overhead gate; per-site seconds become sampled estimates "
    "(avg timed call x calls).  1 = time every call.",
)
PERF_RESERVOIR_MAX = conf.define(
    "auron.perf.reservoir.max", 64,
    "Per-(site, signature) sample reservoir capacity: after this many "
    "calls new samples overwrite slots round-robin, keeping memory "
    "bounded while the EMA tracks the recent distribution.",
)
PERF_SIGNATURES_MAX = conf.define(
    "auron.perf.signatures.max", 8,
    "Distinct abstract signatures tracked per jit site before further "
    "signatures aggregate under '<other>' — the same cardinality guard "
    "jitcheck's retrace-storm detector exists for.",
)
PERF_EMA_ALPHA = conf.define(
    "auron.perf.ema.alpha", 0.2,
    "Smoothing factor of the per-signature wall-time EMA (new = "
    "alpha*sample + (1-alpha)*old).",
)
PERF_PEAK_GBPS = conf.define(
    "auron.perf.peak.gbps", 0.0,
    "Machine peak memory bandwidth (GB/s) used as the roofline "
    "ceiling.  0 (default) = on an accelerator the published peak of "
    "its device_kind (perfscope.DEVICE_PEAK_GBPS; an unknown device is "
    "an error), on the CPU one STREAM-style memcpy probe whose verdict "
    "is cached in auron.perf.peak.path.",
)
PERF_PEAK_PATH = conf.define(
    "auron.perf.peak.path", "",
    "Cache file for the CPU's measured machine-peak verdict (JSON keyed "
    "by platform).  Empty = <repo>/.jax_cache/perf_peak.json.",
)
STATS_STORE_DIR = conf.define(
    "auron.stats.store.dir", "",
    "Arm the durable per-plan-signature statistics store "
    "(runtime/statshist.py): at query terminal the QueryRecord's "
    "wall/queue/exec breakdown, mem peaks, per-exchange observed "
    "{bytes, rows, partitions}, AQE decisions and the perfscope kernel "
    "profile fold into an append-only crash-safe JSONL file under this "
    "directory; on startup the store seeds MemForecaster admission "
    "forecasts, the CostModel's per-(signature, exchange) history (the "
    "learned-initial-plan feed) and the perfscope ledger.  "
    "Empty (default) = OFF, terminal path bit-identical.  In a fleet "
    "the DRIVER owns the store (worker records ship over harvest; "
    "worker processes never write it).",
)
STATS_COMPACT_MAX_RECORDS = conf.define(
    "auron.stats.compact.max.records", 512,
    "Per-run record lines tolerated in the store file before it is "
    "rewritten as one EMA summary line per signature (atomic temp+"
    "rename); with the 30-day signature age cap this bounds the store "
    "however many queries a long-lived server folds.",
)
STATS_REGRESSION_FACTOR = conf.define(
    "auron.stats.regression.factor", 2.0,
    "Baseline regression threshold: a terminal record whose wall, "
    "exec, shuffle-bytes or spill dimension exceeds its signature's "
    "EMA baseline by more than this factor (above per-dimension noise "
    "floors) emits one structured `query.regression` flight-recorder "
    "event naming the offending dimensions, bumps "
    "auron_query_regressions_total{kind}, and lands on GET "
    "/regressions.",
)
STATS_REGRESSION_MIN_RUNS = conf.define(
    "auron.stats.regression.min.runs", 3,
    "Runs a signature's baseline must have folded before regression "
    "detection arms for it — the first executions of a new plan shape "
    "establish the EMA instead of comparing against one cold sample.",
)


def apply_compile_cache() -> Optional[str]:
    """Session-level default for the persistent XLA compilation cache
    (`auron.compile.cache.dir`): a cold stage program costs far more to
    compile than to run, and without the cache every fresh process
    re-pays every compile.  Called by AuronSession and the IT CLI;
    idempotent.  Returns the cache dir in use, or None when disabled.

    The directory is part of the cache's key, so it never moves: where
    `JAX_COMPILATION_CACHE_DIR` is set JAX's own handling stands and
    nothing is set here; otherwise `<repo>/.jax_cache` on device
    backends ('auto') or the explicit path the option names."""
    raw = str(conf.get("auron.compile.cache.dir")).strip()
    if raw in ("", "off", "none", "false"):
        return None
    import jax
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return jax.config.jax_compilation_cache_dir
    if raw == "auto":
        if jax.default_backend() == "cpu":
            return None
        repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        path = os.path.join(repo, ".jax_cache")
    else:
        path = raw
    if jax.config.jax_compilation_cache_dir != path:
        jax.config.update("jax_compilation_cache_dir", path)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 2)
    return path


def _main() -> None:
    """`python -m auron_tpu.config` writes the markdown config reference
    (SparkAuronConfigurationDocGenerator analogue)."""
    import sys
    header = ("# Configuration reference\n\n"
              "Generated by `python -m auron_tpu.config`.\n\n")
    sys.stdout.write(header + conf.generate_doc() + "\n")


if __name__ == "__main__":
    _main()

"""Micro-measurements: TPC-DS q01-shape pipeline through the REAL operator
engine (plan IR -> PhysicalPlanner -> jitted operator kernels), the SPMD
stage compiler, the fused single-kernel ceiling and the kernel-family
profile, next to a vectorized-numpy host oracle.

This is not yet the repo's benchmark (no cells, no bounds): it runs its
workers one after another on whatever device JAX gives and says which one
that was.

- each worker runs in its own child process, one at a time; this parent
  never imports jax, so the chip always belongs to exactly one process;
- there is no CPU stand-in: a worker that fails is reported and the exit
  code is non-zero;
- every summary line is one JSON object carrying `platform`,
  `device_kind` and `device_count`; the unit says `rows/sec/chip` only
  when the platform is `tpu`.

Pipeline (BASELINE.json config #1 shape): filter -> project ->
group-aggregate (sum+count by key) -> broadcast dim-table probe.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

N_ROWS = 1 << 22          # 4M rows
N_KEYS = 4096
BATCH_ROWS = 1 << 20      # 1M-row batches into the engine
_T0 = time.time()


# ---------------------------------------------------------------------------
# data + numpy oracle (host CPU baseline)
# ---------------------------------------------------------------------------

def make_data(n: int, n_keys: int = N_KEYS, dim_rows: int = 4096,
              seed: int = 7):
    import numpy as np
    rng = np.random.default_rng(seed)
    key = rng.integers(0, n_keys, n).astype(np.int64)
    amount = rng.normal(50, 25, n).astype(np.float32)
    disc = rng.uniform(0, 0.3, n).astype(np.float32)
    dim_key = np.arange(dim_rows, dtype=np.int64)
    dim_val = rng.normal(0, 1, dim_rows).astype(np.float32)
    return key, amount, disc, dim_key, dim_val


def numpy_baseline(key, amount, disc, dim_key, dim_val):
    import numpy as np
    keep = amount > 0
    k = key[keep]
    v = (amount * (1.0 - disc))[keep]
    order = np.argsort(k, kind="stable")
    sk, sv = k[order], v[order]
    boundary = np.concatenate([[True], sk[1:] != sk[:-1]])
    seg = np.cumsum(boundary) - 1
    sums = np.bincount(seg, weights=sv)
    counts = np.bincount(seg)
    gkeys = sk[boundary]
    pos = np.clip(np.searchsorted(dim_key, gkeys), 0, len(dim_key) - 1)
    hit = dim_key[pos] == gkeys
    joined = np.where(hit, dim_val[pos], np.nan)
    return gkeys, sums, counts, joined


def host_time_per_run(data, iters: int = 3) -> float:
    numpy_baseline(*data)  # warm
    t0 = time.perf_counter()
    for _ in range(iters):
        numpy_baseline(*data)
    return (time.perf_counter() - t0) / iters


# ---------------------------------------------------------------------------
# worker: engine-path measurement (runs in a subprocess)
# ---------------------------------------------------------------------------

def _build_q01_plan(schema):
    from auron_tpu.ir import expr as E
    from auron_tpu.ir import plan as P
    from auron_tpu.ir.expr import AggExpr, col, lit
    from auron_tpu.ir.schema import DataType
    src = P.FFIReader(schema=schema, resource_id="src")
    dim_schema = None  # set by caller through dim FFI reader
    agg = P.Agg(
        child=P.Projection(
            child=P.Filter(child=src, predicates=(
                E.BinaryExpr(left=col("amount"), op=">", right=lit(0.0)),)),
            exprs=(col("key"),
                   E.BinaryExpr(left=col("amount"), op="*",
                                right=E.BinaryExpr(left=lit(1.0), op="-",
                                                   right=col("disc")))),
            names=("key", "net")),
        exec_mode="single", grouping=(col("key"),), grouping_names=("key",),
        aggs=(AggExpr(fn="sum", children=(col("net"),),
                      return_type=DataType.float64()),
              AggExpr(fn="count", children=(col("net"),),
                      return_type=DataType.int64())),
        agg_names=("s", "c"))
    return agg


def worker_engine() -> dict:
    import numpy as np
    import pyarrow as pa

    import auron_tpu  # noqa: F401
    from auron_tpu.ir import plan as P
    from auron_tpu.ir.expr import col
    from auron_tpu.ir.plan import JoinOn
    from auron_tpu.ir.schema import from_arrow_schema
    from auron_tpu.runtime.executor import execute_plan
    from auron_tpu.runtime.resources import ResourceRegistry

    key, amount, disc, dim_key, dim_val = make_data(N_ROWS)
    t = pa.table({"key": key, "amount": amount, "disc": disc})
    dim = pa.table({"dkey": dim_key, "dval": dim_val})
    res = ResourceRegistry()
    res.put("src", t.to_batches(max_chunksize=BATCH_ROWS))
    res.put("dim", dim.to_batches())
    agg = _build_q01_plan(from_arrow_schema(t.schema))
    plan = P.BroadcastJoin(
        left=agg,
        right=P.FFIReader(schema=from_arrow_schema(dim.schema),
                          resource_id="dim"),
        on=JoinOn(left_keys=(col("key"),), right_keys=(col("dkey"),)),
        join_type="left", broadcast_side="right")

    out = execute_plan(plan, resources=res)      # compile + warm
    n_out = sum(b.num_rows for b in out.batches)
    from auron_tpu.runtime import jitcheck
    warm_counts = jitcheck.compile_counts()
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        r = execute_plan(plan, resources=res)
        # to_arrow on the last batch is the completion barrier
        for b in r.batches:
            b.num_rows
        times.append(time.perf_counter() - t0)
    med = sorted(times)[1]
    # a site recompiling INSIDE the timed loop is a broken cache key,
    # not a slower kernel — name it in the artifact
    retrace_sites = jitcheck.retrace_sites(baseline=warm_counts)
    # perfscope pass: the same warm loop armed — the artifact records
    # the per-site roofline (achieved GB/s vs the measured machine
    # peak) and the armed-over-disarmed overhead ratio the OFF-default
    # claim rests on
    from auron_tpu.runtime import perfscope
    perfscope.reset_state()
    perfscope.configure(True)
    try:
        armed_times = []
        for _ in range(3):
            t0 = time.perf_counter()
            r = execute_plan(plan, resources=res)
            for b in r.batches:
                b.num_rows
            armed_times.append(time.perf_counter() - t0)
        rooflines = perfscope.rooflines()
    finally:
        perfscope.configure(False)
    armed_med = sorted(armed_times)[1]
    # fusion observability: how many fragments/ops the rewriter fused in
    # this plan (runtime/fusion.py), so the artifact records whether the
    # serial number ran fused and at what coverage
    from auron_tpu.config import conf as _conf
    from auron_tpu.runtime.fusion import fuse_plan_cached
    _, fusion_rep = fuse_plan_cached(plan)
    return {"seconds": med, "rows": N_ROWS, "groups": int(n_out),
            "fuse_enabled": bool(_conf.get("auron.fuse.enable")),
            "fused_fragments": fusion_rep.n_fragments,
            "fused_ops": fusion_rep.ops_fused,
            "compile_count": sum(jitcheck.compile_counts().values()),
            "retrace_sites": retrace_sites,
            "perfscope_sites": rooflines.get("sites", {}),
            "machine_peak_gbps": rooflines.get("peak_gbps", 0.0),
            "perfscope_overhead_ratio": round(armed_med / med, 4)
            if med > 0 else 1.0}


def worker_spmd() -> dict:
    """The same q01 pipeline through the SPMD stage compiler: planner IR
    compiled as ONE shard_map program over the device mesh (partial agg ->
    hash exchange -> final agg -> broadcast join), host work reduced to
    the input shard + output gather.  This is the TPU-first engine path —
    the serial per-batch walk is the fallback shape."""
    import numpy as np
    import pyarrow as pa

    import auron_tpu  # noqa: F401
    import jax
    from auron_tpu.frontend.converters import BroadcastJob, ShuffleJob
    from auron_tpu.ir import expr as E
    from auron_tpu.ir import plan as P
    from auron_tpu.ir.expr import AggExpr, col, lit
    from auron_tpu.ir.plan import JoinOn
    from auron_tpu.ir.schema import DataType, from_arrow_schema
    from auron_tpu.parallel.mesh import data_mesh
    from auron_tpu.parallel.stage import execute_plan_spmd

    # On an accelerator the warm loop is dispatch+gather bound (inputs
    # stay device-resident via the stage source cache), so rows/s at 4M
    # rows understates the chip by the ratio of compute to fixed RTT —
    # scale the device working set so the fixed costs amortize (~550MB
    # in HBM at 32M rows; upload is paid once, outside the timed loop).
    # CPU keeps the 4M shape: its wall time is compute-proportional.
    n_rows = int(os.environ.get("AURON_BENCH_SPMD_ROWS", "0")) or \
        (N_ROWS if jax.devices()[0].platform == "cpu" else 1 << 25)
    key, amount, disc, dim_key, dim_val = make_data(n_rows)
    t = pa.table({"key": key, "amount": amount, "disc": disc})
    dim = pa.table({"dkey": dim_key, "dval": dim_val})
    F64 = DataType.float64()
    I64 = DataType.int64()
    src = P.FFIReader(schema=from_arrow_schema(t.schema),
                      resource_id="src")
    partial = P.Agg(
        child=P.Projection(
            child=P.Filter(child=src, predicates=(
                E.BinaryExpr(left=col("amount"), op=">", right=lit(0.0)),)),
            exprs=(col("key"),
                   E.BinaryExpr(left=col("amount"), op="*",
                                right=E.BinaryExpr(left=lit(1.0), op="-",
                                                   right=col("disc")))),
            names=("key", "net")),
        exec_mode="partial", grouping=(col("key"),),
        grouping_names=("key",),
        aggs=(AggExpr(fn="sum", children=(col("net"),), return_type=F64),
              AggExpr(fn="count", children=(col("net"),),
                      return_type=I64)),
        agg_names=("s", "c"))

    class _Ctx:
        pass
    ctx = _Ctx()
    n_dev = len(jax.devices())
    ctx.exchanges = {"ex0": ShuffleJob(
        rid="ex0", child=partial,
        partitioning=P.Partitioning(mode="hash", num_partitions=n_dev,
                                    expressions=(col("key"),)),
        schema=None)}
    ctx.broadcasts = {"bc0": BroadcastJob(
        rid="bc0", child=P.FFIReader(schema=from_arrow_schema(dim.schema),
                                     resource_id="dim"), schema=None)}
    final = P.Agg(
        child=P.IpcReader(schema=None, resource_id="ex0"),
        exec_mode="final", grouping=(col("key"),), grouping_names=("key",),
        aggs=(AggExpr(fn="sum", children=(col("net"),), return_type=F64),
              AggExpr(fn="count", children=(col("net"),),
                      return_type=I64)),
        agg_names=("s", "c"))
    join = P.BroadcastJoin(
        left=final,
        right=P.IpcReader(schema=None, resource_id="bc0"),
        on=JoinOn(left_keys=(col("key"),), right_keys=(col("dkey"),)),
        join_type="left", broadcast_side="right")

    mesh = data_mesh(n_dev)
    sources = {"src": t, "dim": dim}
    out = execute_plan_spmd(join, ctx, mesh, sources)   # compile + warm
    n_out = out.num_rows
    from auron_tpu.runtime import jitcheck
    warm_counts = jitcheck.compile_counts()
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        execute_plan_spmd(join, ctx, mesh, sources)
        times.append(time.perf_counter() - t0)
    med = sorted(times)[1]
    from auron_tpu.parallel.stage import GATHER_STATS
    return {"seconds": med, "rows": n_rows, "groups": int(n_out),
            "n_dev": n_dev, "gather_bytes": GATHER_STATS["bytes"],
            "compile_count": sum(jitcheck.compile_counts().values()),
            "retrace_sites": jitcheck.retrace_sites(
                baseline=warm_counts)}


def worker_profile() -> dict:
    """Micro-profile of the engine's kernel families on the real device
    (VERDICT r1 #7: profile the q01 pipeline before writing Pallas).
    Times each candidate at bench scale so the recorded BENCH artifact
    says which op family dominates — the Pallas budget goes there.

    AURON_PROFILE_ROWS overrides the row count: the MFU measurement
    (VERDICT r4 ask #3) runs at 64M+ rows where families leave the
    dispatch floor and achieved GB/s means something against the HBM
    roofline."""
    import numpy as np

    import auron_tpu  # noqa: F401
    import jax
    import jax.numpy as jnp

    n = int(os.environ.get("AURON_PROFILE_ROWS", 1 << 22))
    n_groups = N_KEYS
    rng = np.random.default_rng(3)
    key64 = jnp.asarray(rng.integers(0, n_groups, n).astype(np.int64))
    vals = jnp.asarray(rng.normal(0, 1, n).astype(np.float64))
    seg_sorted = jnp.sort(jnp.asarray(
        rng.integers(0, n_groups, n).astype(np.int32)))
    probe = jnp.asarray(rng.integers(0, n_groups, n).astype(np.int64))
    table = jnp.asarray(np.sort(rng.integers(0, 1 << 40, n_groups)
                                .astype(np.uint64)))
    idx = jnp.asarray(rng.integers(0, n, n).astype(np.int32))
    mask = jnp.asarray(rng.random(n) < 0.5)

    from auron_tpu.ops.segments import sorted_segment_sum

    from auron_tpu.exprs import hashing as H
    from auron_tpu.columnar.batch import DeviceColumn
    from auron_tpu.ir.schema import DataType

    valid = jnp.ones(n, bool)

    def xla_hash_pid(k, v):
        col = DeviceColumn(DataType.int64(), k, v)
        return H.pmod(H.hash_columns([col], seed=42), 200)

    cands = {
        "argsort_u64": jax.jit(lambda k: jnp.argsort(k.astype(jnp.uint64))),
        "argsort_u32": jax.jit(
            lambda k: jnp.argsort(k.astype(jnp.uint32))),
        "segment_sum_sorted": jax.jit(
            lambda v, s: sorted_segment_sum(v, s, n_groups)),
        "probe_searchsorted": jax.jit(
            lambda t, p: jnp.searchsorted(t, p.astype(jnp.uint64))),
        "gather_rows": jax.jit(lambda v, i: jnp.take(v, i, axis=0)),
        "filter_compact": jax.jit(
            lambda m: jnp.nonzero(m, size=n, fill_value=0)[0]
            .astype(jnp.int32)),
        # head-to-head: the ONE existing Pallas kernel vs its XLA form —
        # BENCH records whether it pays (VERDICT r2 #9: decide by
        # numbers, keep or delete next round)
        "hash_pid_xla": jax.jit(xla_hash_pid),
    }
    args = {
        "argsort_u64": (key64,), "argsort_u32": (key64,),
        "segment_sum_sorted": (vals, seg_sorted),
        "probe_searchsorted": (table, probe),
        "gather_rows": (vals, idx), "filter_compact": (mask,),
        "hash_pid_xla": (key64, valid),
    }
    # per-STRATEGY timings (the kernel-floor PR): the radix pack-sort vs
    # the comparator argsort it replaces, and the bucket-partitioned
    # probe vs the double searchsorted — so the bench trajectory can SEE
    # the swap (argsort_u64_ms vs radix_sort_u64_ms) instead of inferring
    # it from the headline
    from auron_tpu.ops import strategy as KS
    from auron_tpu.ops.joins.kernel import bounded_probe, build_probe_index
    from auron_tpu.ops.radix_sort import radix_sort_indices
    cands["radix_sort_u64"] = jax.jit(
        lambda k: radix_sort_indices([k.astype(jnp.uint64)], [64]))
    args["radix_sort_u64"] = (key64,)
    cands["radix_sort_u32"] = jax.jit(
        lambda k: radix_sort_indices([k.astype(jnp.uint32)], [32]))
    args["radix_sort_u32"] = (key64,)
    # the partitioned probe sees what join probes see: uniform 64-bit
    # murmur HASHES (the 2^40-bounded `table` above would collapse every
    # key into radix bucket 0 and measure the degenerate span instead)
    jtable = jnp.sort(jnp.asarray(
        rng.integers(0, 1 << 63, n_groups).astype(np.uint64)))
    jprobe = jnp.asarray(rng.integers(0, 1 << 63, n).astype(np.uint64))
    probe_index = build_probe_index(jtable)
    cands["probe_partitioned"] = jax.jit(
        lambda p: bounded_probe(probe_index, p)[0])
    args["probe_partitioned"] = (jprobe,)
    try:
        from auron_tpu.ops import kernels_pallas as KP
        if KP.supported([DeviceColumn(DataType.int64(), key64, valid)]):
            cands["hash_pid_pallas"] = jax.jit(
                lambda k, v: KP.hash_partition_ids_i64(k, v, 200))
            args["hash_pid_pallas"] = (key64, valid)
    except Exception:  # noqa: BLE001 - pallas unavailable on this backend
        pass
    # minimal algorithmic bytes per family (read input once + write
    # output once — the roofline convention; VERDICT r3 #6: "at
    # dispatch floor" needs a denominator to be distinguishable from
    # "slow").  g = table/group count.
    g = n_groups
    bytes_model = {
        "argsort_u64": n * 8 + n * 4,
        "argsort_u32": n * 4 + n * 4,
        "radix_sort_u64": n * 8 + n * 4,
        "radix_sort_u32": n * 4 + n * 4,
        "segment_sum_sorted": n * 8 + n * 4 + g * 8,
        "probe_searchsorted": n * 8 + g * 8 + n * 4,
        "probe_partitioned": n * 8 + g * 8 + n * 4,
        "gather_rows": n * 8 + n * 4 + n * 8,
        "filter_compact": n * 1 + n * 4,
        "hash_pid_xla": n * 8 + n * 4,
        "hash_pid_pallas": n * 8 + n * 4,
    }
    # published HBM peak by device kind (perfscope.DEVICE_PEAK_GBPS; an
    # accelerator the table does not know is an error).  A CPU run has
    # no HBM roofline to report against.
    from auron_tpu.runtime import perfscope
    dev = jax.devices()[0]
    hbm_gbps = None if dev.platform == "cpu" else \
        perfscope.device_peak_gbps(dev.device_kind)
    prof = {}
    roofline = {}
    for name, fn in cands.items():
        a = args[name]
        jax.block_until_ready(fn(*a))       # compile + warm
        times = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(fn(*a))
            times.append(time.perf_counter() - t0)
        sec = sorted(times)[1]
        prof[name + "_ms"] = round(sec * 1e3, 3)
        nbytes = bytes_model.get(name)
        if nbytes:
            gbps = nbytes / sec / 1e9
            entry = {"bytes": nbytes, "achieved_gbps": round(gbps, 2)}
            if hbm_gbps:
                entry["pct_hbm_roofline"] = round(100 * gbps / hbm_gbps, 2)
            roofline[name] = entry
    return {"profile": prof, "rows": n, "roofline": roofline,
            "hbm_roofline_gbps": hbm_gbps,
            # what `auto` resolves to on THIS backend at the profiled
            # shapes — the artifact records which strategy the engine
            # actually ran with, next to both strategies' timings
            "kernel_strategy": {
                "sort": KS.sort_strategy(n),
                "join_probe": KS.join_probe_strategy(n_groups),
                "group": KS.group_strategy(256)}}


def worker_fused() -> dict:
    """The fused single-kernel ceiling (K iterations inside one lax.scan,
    one fetch as barrier — isolates device compute from dispatch)."""
    import numpy as np

    import auron_tpu  # noqa: F401
    import jax
    import jax.numpy as jnp
    from jax import lax
    from auron_tpu.parallel.spmd import make_single_chip_step

    key, amount, disc, dim_key, dim_val = make_data(1 << 21)
    valid = np.ones(len(key), bool)
    inner = make_single_chip_step()
    iters = 10

    def many(key, amount, disc, valid, dim_key, dim_val, k):
        def body(carry, i):
            amt = amount + i.astype(jnp.float32) * 1e-6
            out = inner(key, amt, disc, valid, dim_key, dim_val)
            return carry + out[4], None
        total, _ = lax.scan(body, jnp.int64(0), jnp.arange(k))
        return total

    f = jax.jit(many, static_argnames="k")
    dev = [jax.device_put(a) for a in
           (key, amount, disc, valid, dim_key, dim_val)]
    float(f(*dev, k=iters))
    times = []
    for _ in range(3):
        t0 = time.perf_counter()
        float(f(*dev, k=iters))
        times.append((time.perf_counter() - t0) / iters)
    med = sorted(times)[1]
    return {"seconds": med, "rows": 1 << 21}


# ---------------------------------------------------------------------------
# orchestrator
# ---------------------------------------------------------------------------

def worker_serde() -> dict:
    """Exchange data-plane numbers (the PR 14 headline pair):

    1. serde microbench — v1 (arrow-IPC frames) vs v2 (raw device
       layout, schema once) ROUND TRIP (serialize + deserialize +
       device ingest) on a 1M-row multi-column batch, at codec none
       (the serde itself) and at the configured shuffle codec; plus
       the copy_count proof that the v2 fixed-width fetch->device
       path performs ZERO decode copies.
    2. exchange A/B — an exchange-heavy corpus query (q94n: two
       hash exchanges whose map roots fuse) run serial-path with the
       full data plane ON (v2 + pid fusion + pipelining) vs OFF,
       interleaved in ONE process, results bit-identical.
    """
    import io as _io
    import tempfile

    import numpy as np
    import pyarrow as pa

    import auron_tpu  # noqa: F401
    from auron_tpu.columnar import serde
    from auron_tpu.columnar.batch import Batch
    from auron_tpu.config import conf
    from auron_tpu.ir.schema import DataType, Field, Schema

    n = 1 << 20
    rng = np.random.default_rng(7)
    schema = Schema((Field("k", DataType.int64()),
                     Field("v", DataType.float64()),
                     Field("d", DataType.int32()),
                     Field("s", DataType.string())))
    rb = pa.RecordBatch.from_arrays(
        [pa.array(rng.integers(0, 1 << 40, n)), pa.array(rng.random(n)),
         pa.array(rng.integers(0, 100, n).astype(np.int32)),
         pa.array([f"cat{i % 97:04d}" for i in range(n)])],
        names=["k", "v", "d", "s"])
    b = Batch.from_arrow(rb, schema=schema)
    raw_bytes = b.mem_bytes()

    def touch(x):
        for c in x.columns:
            if hasattr(c, "data") and hasattr(c.data, "block_until_ready"):
                c.data.block_until_ready()

    def v1_rt():
        sink = _io.BytesIO()
        serde.write_one_batch(b.to_arrow(), sink)
        sink.seek(0)
        out = [Batch.from_arrow(x, schema=schema)
               if isinstance(x, pa.RecordBatch) else x
               for x in serde.read_batches(sink)]
        touch(out[0])

    def v2_rt():
        sink = _io.BytesIO()
        sink.write(serde.encode_stream_header(schema))
        serde.encode_batch_v2(b, out=sink)
        sink.seek(0)
        out = list(serde.read_batches(sink))
        touch(out[0])

    def best_ms(fn, iters=3):
        times = []
        for _ in range(iters):
            t0 = time.perf_counter_ns()
            fn()
            times.append((time.perf_counter_ns() - t0) / 1e6)
        return min(times)

    out: dict = {"rows": n, "batch_bytes": raw_bytes}
    for codec in ("none", str(conf.get("auron.shuffle.compression.codec"))):
        with conf.scoped({"auron.shuffle.compression.codec": codec}):
            v1_rt(); v2_rt()   # warm (compiles nothing, primes allocs)
            t1, t2 = best_ms(v1_rt), best_ms(v2_rt)
        key = "none" if codec == "none" else "codec"
        out[f"serde_v1_ms_{key}"] = round(t1, 1)
        out[f"serde_v2_ms_{key}"] = round(t2, 1)
        out[f"serde_speedup_v2_{key}"] = round(t1 / t2, 2)
    out["shuffle_serde_mbps"] = round(
        raw_bytes / (out["serde_v2_ms_none"] / 1e3) / (1 << 20))
    out["shuffle_serde_mbps_v1"] = round(
        raw_bytes / (out["serde_v1_ms_none"] / 1e3) / (1 << 20))
    # the zero-decode-copy proof on the fetch->device path
    sink = _io.BytesIO()
    sink.write(serde.encode_stream_header(schema))
    with conf.scoped({"auron.shuffle.compression.codec": "none"}):
        serde.encode_batch_v2(b, out=sink)
    sink.seek(0)
    serde.reset_copy_count()
    touch(list(serde.read_batches(sink))[0])
    out["exchange_copy_count"] = serde.copy_count()
    serde.reset_copy_count()

    # exchange-heavy interleaved A/B (serial path = the exchange path)
    from auron_tpu.frontend.session import AuronSession
    from auron_tpu.it import datagen, oracle, queries
    catalog = datagen.generate(tempfile.mkdtemp(prefix="auron-serde-ab-"),
                               sf=0.01)
    OFF = {"auron.serde.format.version": 1,
           "auron.shuffle.pid.fuse.enable": False,
           "auron.shuffle.pipeline.depth": 1}
    BASE = {"auron.spmd.singleDevice.enable": False}

    def run_q(extra):
        with conf.scoped({**BASE, **extra}):
            sess = AuronSession(foreign_engine=oracle.PyArrowEngine())
            t0 = time.perf_counter()
            res = sess.execute(queries.build("q94n", catalog))
            return time.perf_counter() - t0, res.table

    run_q({}); run_q(OFF)     # warm both paths
    on_t, off_t = [], []
    identical = True
    for _ in range(5):
        dt_on, tab_on = run_q({})
        dt_off, tab_off = run_q(OFF)
        on_t.append(dt_on)
        off_t.append(dt_off)
        identical = identical and tab_on.equals(tab_off)
    on_t.sort(); off_t.sort()
    out["exchange_ab_query"] = "q94n"
    out["exchange_ab_on_ms"] = round(on_t[len(on_t) // 2] * 1e3)
    out["exchange_ab_off_ms"] = round(off_t[len(off_t) // 2] * 1e3)
    out["exchange_ab_ratio"] = round(
        off_t[len(off_t) // 2] / on_t[len(on_t) // 2], 3)
    out["exchange_ab_identical"] = identical
    from auron_tpu.runtime import counters
    out["exchange_bytes_pushed"] = counters.get("shuffle_bytes_pushed")
    out["exchange_bytes_fetched"] = counters.get("shuffle_bytes_fetched")
    return out


def worker_aqe() -> dict:
    """Adaptive-execution numbers (the PR 15 headline):

    1. interleaved in-process A/B on a coalesce/skew-sensitive corpus
       query, `auron.adaptive.enable` on vs off on the serial exchange
       path, results value-identical — the no-regression acceptance
       gate (tools/aqe_check.sh asserts the decision counters).
    2. per-exchange observed sizes + the structured decisions from the
       AQE-on run (`aqe_decisions`, `exchange_bytes`), so the artifact
       records WHAT the replanner did, not just how fast it was.
    3. the exchange codec-policy delta: the in-process service at
       codec.local=none (default) vs forced zlib on the same query —
       the compress-only-to-decompress round trip the policy removed.
    """
    import tempfile

    import auron_tpu  # noqa: F401
    from auron_tpu.config import conf
    from auron_tpu.frontend.session import AuronSession
    from auron_tpu.it import compare, datagen, oracle, queries

    catalog = datagen.generate(tempfile.mkdtemp(prefix="auron-aqe-ab-"),
                               sf=0.01)
    BASE = {"auron.spmd.singleDevice.enable": False,
            "auron.force.shuffled.hash.join": True}
    ON = {**BASE, "auron.adaptive.enable": True}
    name = "q01"

    def run_q(extra):
        with conf.scoped({**BASE, **extra}):
            sess = AuronSession(foreign_engine=oracle.PyArrowEngine())
            t0 = time.perf_counter()
            res = sess.execute(queries.build(name, catalog))
            return time.perf_counter() - t0, res

    run_q({}); run_q(ON)          # warm both paths
    on_t, off_t = [], []
    identical = True
    decisions = []
    exchange_bytes = []
    plan = queries.build(name, catalog)
    for _ in range(5):
        dt_on, r_on = run_q(ON)
        dt_off, r_off = run_q({})
        on_t.append(dt_on)
        off_t.append(dt_off)
        identical = identical and compare.compare_tables(
            r_on.table, r_off.table,
            ordered=compare.plan_is_ordered(plan)) is None
        decisions = r_on.aqe_decisions
        exchange_bytes = [
            {"exchange": s["exchange"], "partitions": s["partitions"],
             "bytes_out": s["bytes_out"]}
            for s in r_on.exchange_stats]
    on_t.sort(); off_t.sort()
    out = {
        "aqe_ab_query": name,
        "aqe_ab_on_ms": round(on_t[len(on_t) // 2] * 1e3),
        "aqe_ab_off_ms": round(off_t[len(off_t) // 2] * 1e3),
        "aqe_ab_ratio": round(
            off_t[len(off_t) // 2] / on_t[len(on_t) // 2], 3),
        "aqe_ab_identical": identical,
        "aqe_decisions": decisions,
        "exchange_bytes": exchange_bytes,
    }

    # codec-policy delta: default local `none` vs forced zlib
    zlib_t, none_t = [], []
    for _ in range(3):
        dt_none, _r = run_q({})
        dt_zlib, _r = run_q({"auron.shuffle.codec.local": "zlib"})
        none_t.append(dt_none)
        zlib_t.append(dt_zlib)
    out["codec_local_none_ms"] = round(min(none_t) * 1e3)
    out["codec_local_zlib_ms"] = round(min(zlib_t) * 1e3)
    out["codec_local_ratio"] = round(min(zlib_t) / max(min(none_t),
                                                       1e-9), 3)
    return out


WORKERS = ("engine", "spmd", "fused", "profile", "serde", "aqe")


def _device_info() -> dict:
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform,
            "device_kind": devs[0].device_kind,
            "device_count": len(devs)}


def _run_worker(mode: str) -> dict:
    """One worker in a child of its own: a chip belongs to one process
    at a time, and this parent never touches jax."""
    env = dict(os.environ)
    # compilation observability (runtime/jitcheck.py): workers count
    # jitted-program traces per site so a result can tell "kernel got
    # slower" from "kernel got recompiled".  Probes fire at TRACE time
    # only — the warm timed loops run the compiled path and pay nothing.
    env.setdefault("AURON_TPU_AURON_JITCHECK_ENABLE", "1")
    here = os.path.dirname(os.path.abspath(__file__))
    p = subprocess.run([sys.executable, os.path.abspath(__file__),
                        "--worker", mode],
                       capture_output=True, text=True, env=env, cwd=here)
    for line in reversed(p.stdout.strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            return json.loads(line)
    raise RuntimeError(
        f"worker {mode} rc={p.returncode}: {p.stderr.strip()[-400:]}")


def _unit(result: dict) -> str:
    """`/chip` is a claim about an accelerator: only a TPU run may
    make it."""
    if result.get("platform") == "tpu":
        return "rows/sec/chip (tpu)"
    return f"rows/sec ({result.get('platform')} run, not a chip number)"


def _summarize(results: dict, baseline_rps: float,
               diagnostics: list) -> dict:
    """Fold whatever has landed so far into ONE contract-shaped JSON
    object.  Called (and flushed) after EVERY worker so a driver kill
    still leaves a valid artifact on the last stdout line."""
    profile = results.get("profile")
    fused = results.get("fused")
    engine = results.get("engine")
    spmd = results.get("spmd")
    # the SPMD stage compiler IS the engine path (planner IR -> one
    # shard_map program); the serial per-batch walk is its fallback.
    # Headline = the faster of the two engine modes by ROWS/S — the
    # spmd working set is platform-scaled, so comparing raw seconds
    # across different row counts picked the wrong mode (ADVICE r5).
    def _rps(r):
        return r["rows"] / r["seconds"]
    if spmd is not None and (engine is None or _rps(spmd) > _rps(engine)):
        engine_any, mode_name = spmd, "spmd_stage"
    else:
        engine_any, mode_name = engine, "serial"

    if engine_any is not None:
        rps = engine_any["rows"] / engine_any["seconds"]
        out = {
            "metric": "engine_q01_rows_per_sec",
            "value": round(rps),
            "unit": _unit(engine_any),
            "vs_baseline": round(rps / baseline_rps, 3),
            "engine_mode": mode_name,
        }
        if spmd is not None:
            out["spmd_rows_per_sec"] = round(spmd["rows"] /
                                             spmd["seconds"])
            # the SPMD working set is scaled per platform (engine stays
            # at 4M): cross-platform rows/s comparisons must account for
            # the shape difference (ADVICE r5)
            out["spmd_working_set_rows"] = spmd["rows"]
            if spmd["rows"] != N_ROWS:
                out["working_set_note"] = (
                    f"spmd measured at {spmd['rows']} rows vs engine "
                    f"{N_ROWS}; rows/s are not shape-comparable across "
                    f"platforms")
        if engine is not None:
            out["serial_rows_per_sec"] = round(engine["rows"] /
                                               engine["seconds"])
            out["fuse_enabled"] = engine.get("fuse_enabled")
            out["fused_fragments"] = engine.get("fused_fragments")
            out["fused_ops"] = engine.get("fused_ops")
            if engine.get("perfscope_sites"):
                # per-jit-site roofline from the armed warm loop (the
                # live-ledger view; the microbench roofline from the
                # profile worker lands under the same key below when
                # that worker runs too)
                out.setdefault("kernel_roofline", {})["perfscope_sites"] \
                    = engine["perfscope_sites"]
                out["machine_peak_gbps"] = engine.get("machine_peak_gbps")
                out["perfscope_overhead_ratio"] = \
                    engine.get("perfscope_overhead_ratio")
    elif fused is not None:
        rps = fused["rows"] / fused["seconds"]
        out = {
            "metric": "fused_query_step_rows_per_sec",
            "value": round(rps),
            "unit": _unit(fused),
            "vs_baseline": round(rps / baseline_rps, 3),
        }
    else:
        out = {
            "metric": "engine_q01_rows_per_sec",
            "value": 0,
            "unit": "rows/sec (pending)",
            "vs_baseline": 0.0,
            "error": "no engine measurement landed yet",
        }
    if fused is not None:
        out["fused_rows_per_sec"] = round(fused["rows"] / fused["seconds"])
        # the remaining host-orchestration gap: single-fused-kernel
        # ceiling vs the serial engine (the figure later PRs track; the
        # pipeline-fusion PR closes it from ~80x)
        if engine is not None:
            out["fusion_gap"] = round(
                (fused["rows"] / fused["seconds"]) /
                (engine["rows"] / engine["seconds"]), 1)
    if profile is not None:
        # ONE stable key across platforms (r04 used kernel_profile_ms,
        # r05 renamed the CPU run kernel_profile_cpu_fallback_ms and the
        # trajectory reader had to know both): the profile always lands
        # under kernel_profile_ms and kernel_profile_platform is the
        # device-evidence qualifier — cpu numbers still say NOTHING
        # about the chip (VERDICT r4 weak #1), the qualifier is how a
        # reader knows
        out["kernel_profile_ms"] = profile.get("profile")
        out["kernel_profile_platform"] = profile.get("platform")
        if profile.get("kernel_strategy"):
            out["kernel_strategy"] = profile["kernel_strategy"]
        if profile.get("roofline"):
            # merge, don't overwrite: the engine worker may already have
            # folded its live per-site table under perfscope_sites
            out.setdefault("kernel_roofline", {}).update(
                profile["roofline"])
            out["hbm_roofline_gbps"] = profile.get("hbm_roofline_gbps")
            out["device_kind"] = profile.get("device_kind")
    sd = results.get("serde")
    if sd is not None:
        # the PR 14 data-plane numbers (BENCH_r06 reads the delta):
        # v2-vs-v1 round-trip throughput, the zero-copy proof, and the
        # interleaved exchange A/B with the whole plane on vs off
        for k in ("shuffle_serde_mbps", "shuffle_serde_mbps_v1",
                  "serde_speedup_v2_none", "serde_speedup_v2_codec",
                  "exchange_copy_count", "exchange_ab_query",
                  "exchange_ab_ratio", "exchange_ab_identical",
                  "exchange_bytes_pushed", "exchange_bytes_fetched"):
            if k in sd:
                out[k] = sd[k]
    aq = results.get("aqe")
    if aq is not None:
        # the PR 15 adaptive-execution numbers (BENCH_r06 notes):
        # interleaved A/B + the decision audit + the codec-policy delta
        for k in ("aqe_ab_query", "aqe_ab_on_ms", "aqe_ab_off_ms",
                  "aqe_ab_ratio", "aqe_ab_identical", "aqe_decisions",
                  "exchange_bytes", "codec_local_none_ms",
                  "codec_local_zlib_ms", "codec_local_ratio"):
            if k in aq:
                out[k] = aq[k]
    # top-level platform = whatever produced the HEADLINE metric
    headline = engine_any if engine_any is not None else fused
    if headline is not None:
        for k in ("platform", "device_kind", "device_count"):
            out[k] = headline.get(k)
    out["baseline_rows_per_sec"] = round(baseline_rps)
    out["elapsed_s"] = round(time.time() - _T0, 1)
    if diagnostics:
        out["diagnostics"] = diagnostics[:6]
    return out


def main() -> int:
    diagnostics: list = []
    data = make_data(N_ROWS)
    host_t = host_time_per_run(data)
    baseline_rps = N_ROWS / host_t

    results: dict = {}
    # one full summary line the moment each result lands, so a killed
    # run still leaves a valid last line
    for mode in WORKERS:
        try:
            results[mode] = _run_worker(mode)
        except (RuntimeError, ValueError) as e:
            diagnostics.append(f"{mode}: {str(e)[:300]}")
        print(json.dumps(_summarize(results, baseline_rps, diagnostics)),
              flush=True)
    return 1 if diagnostics else 0


if __name__ == "__main__":
    if len(sys.argv) >= 3 and sys.argv[1] == "--worker":
        fn = {"engine": worker_engine, "fused": worker_fused,
              "profile": worker_profile, "spmd": worker_spmd,
              "serde": worker_serde, "aqe": worker_aqe}[sys.argv[2]]
        from auron_tpu.config import apply_compile_cache
        apply_compile_cache()
        out = fn()
        out.update(_device_info())
        print(json.dumps(out))
    else:
        sys.exit(main())

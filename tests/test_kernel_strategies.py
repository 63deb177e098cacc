"""Kernel-strategy equivalence suite (ISSUE 7: kernel-floor demolition).

Every alternative kernel the strategy layer (ops/strategy.py) can pick
must be provably equivalent to its reference:

- radix pack-sort (ops/radix_sort.py) vs np.lexsort / np.argsort stable
  semantics — duplicate keys, descending (~flipped) words, null-rank
  words, live masks, randomized capacities;
- bucket-partitioned join probe (ops/joins/kernel.py ProbeIndex) vs the
  double-searchsorted range scan — bit-identical (lo, counts), and
  whole-join results identical across strategies for every join flavor;
- one-hot group reduce (ops/hash_group.py) vs jax.ops.segment_* —
  exact for ints, ulp-tolerant for float sums (different reduction
  order), identical through a real agg plan;
- the sort spill-merge invariant: spilled sorted runs merge identically
  (ops/sort.py host merger) regardless of which device sort strategy
  produced them.

Fast cases are tier-1; the kernel_check.sh script test (microbench +
auto-beats-legacy gate) and the forced-strategy chaos sweep ride
`-m slow` like chaos_check/mem_check.
"""

import os
import subprocess

import numpy as np
import pyarrow as pa
import pytest

import jax
import jax.numpy as jnp

from auron_tpu.columnar.batch import Batch
from auron_tpu.config import conf
from auron_tpu.ir import expr as E
from auron_tpu.ir.expr import AggExpr, SortExpr, col, lit
from auron_tpu.ir.schema import DataType, from_arrow_schema
from auron_tpu.memmgr.manager import reset_manager
from auron_tpu.ops import strategy as S
from auron_tpu.ops.base import TaskContext
from auron_tpu.ops.basic import MemoryScanExec
from auron_tpu.ops.radix_sort import (
    num_passes, radix_sort_indices, stable_argsort_flags,
    stable_argsort_u64,
)
from auron_tpu.ops.sort import SortExec
from auron_tpu.ops.sort_keys import lexsort_indices_live

RADIX = {"auron.kernel.sort.strategy": "radix"}
ARGSORT = {"auron.kernel.sort.strategy": "argsort"}
PARTITIONED = {"auron.kernel.join.probe.strategy": "partitioned",
               "auron.kernel.join.partitioned.min.rows": 1}
SEARCHSORTED = {"auron.kernel.join.probe.strategy": "searchsorted"}
ALL_NEW = {"auron.kernel.sort.strategy": "radix",
           "auron.kernel.sort.radix.min.rows": 1,
           "auron.kernel.join.probe.strategy": "partitioned",
           "auron.kernel.join.partitioned.min.rows": 1,
           # the onehot ceiling still binds (it is n*G work); batches
           # under it take the one-hot kernel, the rest stay scatter
           "auron.kernel.group.strategy": "onehot"}


# ---------------------------------------------------------------------------
# radix pack-sort vs numpy references
# ---------------------------------------------------------------------------

def _np_reference_perm(words, bits, live):
    padr = np.where(live, np.uint64(0), np.uint64(1))
    keys = [w.astype(np.uint64) & np.uint64((1 << b) - 1)
            for w, b in zip(words, bits)]
    return np.lexsort(tuple(reversed([padr] + keys)))


@pytest.mark.slow   # PR 12 tier-1 re-split (7.4s; dispatch-parity +
#                     stable-argsort + sort-exec tests keep the gate,
#                     kernel_check.sh runs the full suite nightly)
def test_radix_sort_matches_np_lexsort_randomized():
    rng = np.random.default_rng(42)
    for trial in range(25):
        cap = int(rng.integers(2, 4000))
        n = int(rng.integers(0, cap + 1))
        nw = int(rng.integers(1, 4))
        words, bits = [], []
        for _ in range(nw):
            kind = int(rng.integers(0, 4))
            if kind == 0:       # wide u64
                w = rng.integers(0, 1 << 63, cap).astype(np.uint64)
                b = 64
            elif kind == 1:     # narrow-int u32 word
                w = rng.integers(0, 1 << 31, cap).astype(np.uint32)
                b = 32
            elif kind == 2:     # null-rank / bool word
                w = rng.integers(0, 2, cap).astype(np.uint32)
                b = 1
            else:               # duplicate-heavy u64 (stability stress)
                w = rng.integers(0, 5, cap).astype(np.uint64)
                b = 64
            if rng.random() < 0.3:
                w = ~w          # descending flip
            words.append(w)
            bits.append(b)
        live = np.arange(cap) < n
        got = np.asarray(radix_sort_indices(
            [jnp.asarray(w) for w in words], bits, jnp.asarray(live)))
        ref = _np_reference_perm(words, bits, live)
        np.testing.assert_array_equal(got, ref, err_msg=f"trial {trial}")


def test_stable_argsort_u64_matches_np_stable():
    rng = np.random.default_rng(7)
    for dup_range in (3, 1 << 20):
        k = rng.integers(0, dup_range, 3000).astype(np.uint64)
        got = np.asarray(stable_argsort_u64(jnp.asarray(k)))
        np.testing.assert_array_equal(got, np.argsort(k, kind="stable"))


def test_stable_argsort_flags_matches_np_stable():
    rng = np.random.default_rng(8)
    f = rng.random(2000) < 0.5
    got = np.asarray(stable_argsort_flags(jnp.asarray(f)))
    np.testing.assert_array_equal(got, np.argsort(f, kind="stable"))


def test_lexsort_dispatch_parity_radix_vs_argsort():
    """lexsort_indices_live must return the identical permutation under
    either strategy — the swap is invisible to every consumer."""
    rng = np.random.default_rng(3)
    for cap, n in ((1, 1), (5, 3), (777, 700), (2048, 2048)):
        w64 = jnp.asarray(rng.integers(0, 9, cap).astype(np.uint64))
        wn = jnp.asarray(rng.integers(0, 2, cap).astype(np.uint32))
        live = jnp.asarray(np.arange(cap) < n)
        with conf.scoped(dict(ARGSORT)):
            p0 = np.asarray(lexsort_indices_live([wn, w64], live, [1, 64]))
        with conf.scoped(dict(RADIX, **{
                "auron.kernel.sort.radix.min.rows": 1})):
            p1 = np.asarray(lexsort_indices_live([wn, w64], live, [1, 64]))
        np.testing.assert_array_equal(p0, p1)


def test_num_passes_word_packing():
    # (pad, null, u64) at 4k rows: u64 splits, null+pad pack in -> 2
    assert num_passes([1, 64], 4096, with_live=True) == 2
    # narrow-int key with null word packs into ONE pass
    assert num_passes([1, 32], 4096, with_live=True) == 1
    # dtype-width-claimed null word costs the packing win
    assert num_passes([32, 32], 4096, with_live=True) == 2


# ---------------------------------------------------------------------------
# partitioned probe vs double searchsorted
# ---------------------------------------------------------------------------

@pytest.mark.slow   # PR 18 tier-1 re-split (10.1s; the non-randomized
# bounded-probe regressions stay fast)
def test_bounded_probe_matches_searchsorted_randomized():
    from auron_tpu.ops.joins.kernel import bounded_probe, build_probe_index
    rng = np.random.default_rng(9)
    for trial in range(12):
        cap = int(rng.integers(4, 3000))
        # duplicate-heavy values spread across radix buckets, plus the
        # build null sentinel in some trials
        vals = rng.integers(0, 60, cap).astype(np.uint64) * \
            np.uint64(0x0400000000000000)
        if trial % 3 == 0:
            vals[: cap // 4] = np.uint64(0xFFFFFFFFFFFFFFFF)
        sh = np.sort(vals)
        idx = build_probe_index(jnp.asarray(sh))
        ph = rng.integers(0, 64, 500).astype(np.uint64) * \
            np.uint64(0x0400000000000000)
        lo, cnt = bounded_probe(idx, jnp.asarray(ph))
        ref_lo = np.searchsorted(sh, ph, side="left")
        ref_cnt = np.searchsorted(sh, ph, side="right") - ref_lo
        np.testing.assert_array_equal(np.asarray(cnt), ref_cnt,
                                      err_msg=f"trial {trial}")
        hit = ref_cnt > 0
        np.testing.assert_array_equal(np.asarray(lo)[hit], ref_lo[hit],
                                      err_msg=f"trial {trial}")


def test_bounded_probe_degenerate_single_value():
    """All build rows one hash value: one bucket holds everything, the
    index degrades to span=1 over the dedup'd values and stays exact."""
    from auron_tpu.ops.joins.kernel import bounded_probe, build_probe_index
    sh = np.full(512, 0x1234, np.uint64)
    idx = build_probe_index(jnp.asarray(sh))
    assert idx.iters == 1   # span.bit_length(): span 1 -> one iteration
    lo, cnt = bounded_probe(idx, jnp.asarray(
        np.array([0x1234, 0x1235, 0], np.uint64)))
    assert list(np.asarray(cnt)) == [512, 0, 0]
    assert int(np.asarray(lo)[0]) == 0


def test_bounded_probe_power_of_two_span_regression():
    """PR 15 regression: `iters = ceil(log2(span))` was ONE iteration
    short exactly when the max bucket span is a POWER OF TWO — a
    bucket holding 2^k distinct hashes could stop the bounded search
    one slot before the match and report a miss (surfaced as a lost
    anti-join match when AQE's broadcast-converted builds produced
    tiny dedup'd tables; q16a/q06a/q17m/q38i/q45s/q50c/q87a corpus
    diffs).  Exact formula: span.bit_length()."""
    from auron_tpu.ops.joins.kernel import bounded_probe, build_probe_index
    # two distinct hashes in ONE radix bucket (equal top 16 bits):
    # max span = 2, the minimal failing power of two
    h = np.array([0x1234567800000000, 0x1234567800000001], np.uint64)
    idx = build_probe_index(jnp.asarray(np.sort(h)), b_bits=16)
    assert idx.iters == 2
    lo, cnt = bounded_probe(idx, jnp.asarray(h))
    assert list(np.asarray(cnt)) == [1, 1]   # the upper slot must hit
    assert list(np.asarray(lo)) == [0, 1]
    # and every power-of-two span up to 64, probing every member
    for m in range(1, 7):
        n = 1 << m
        vals = (np.uint64(0x1234567800000000) +
                np.arange(n, dtype=np.uint64))
        idx = build_probe_index(jnp.asarray(vals), b_bits=16)
        _lo, cnt = bounded_probe(idx, jnp.asarray(vals))
        assert np.asarray(cnt).tolist() == [1] * n, f"span {n}"


def _run_join(rows_l, rows_r, join_type, scope):
    from auron_tpu.ir.plan import JoinOn
    from auron_tpu.ops.joins.exec import HashJoinExec

    def scan(rows, names):
        t = pa.Table.from_pylist(rows)
        return MemoryScanExec(
            from_arrow_schema(t.schema),
            [Batch.from_arrow(b) for b in t.to_batches(max_chunksize=64)])

    with conf.scoped(dict(scope)):
        j = HashJoinExec(scan(rows_l, "l"), scan(rows_r, "r"),
                         JoinOn(left_keys=(col("k"),),
                                right_keys=(col("k2"),)),
                         join_type)
        out = [b.to_arrow() for b in j.execute_with_metrics(TaskContext())]
    if not out:
        return []
    return pa.Table.from_batches(out).to_pylist()


@pytest.mark.parametrize("join_type", ["inner", "left", "full",
                                       "left_semi", "left_anti"])
def test_join_results_identical_across_probe_strategies(join_type):
    """Whole-join equivalence: pair sets AND emission order must match
    between probe strategies (the partitioned index returns the same
    (lo, counts) over the same sorted array, so even row order agrees).
    Duplicate keys on both sides + null keys + misses."""
    rng = np.random.default_rng(13)
    rows_l = [{"k": (int(rng.integers(0, 40)) if rng.random() > 0.1
                     else None), "lv": i} for i in range(400)]
    rows_r = [{"k2": (int(rng.integers(0, 50)) if rng.random() > 0.1
                      else None), "rv": i} for i in range(300)]
    a = _run_join(rows_l, rows_r, join_type, SEARCHSORTED)
    b = _run_join(rows_l, rows_r, join_type, PARTITIONED)
    assert a == b
    # and as an unordered multiset (the ISSUE's weaker contract, pinned
    # separately in case emission order is ever relaxed on purpose)
    key = lambda r: tuple(sorted((k, str(v)) for k, v in r.items()))
    assert sorted(map(key, a)) == sorted(map(key, b))


def test_partitioned_probe_kernel_family_built():
    """The strategy flip must show up in the kernel cache as the
    partitioned range-kernel family actually building."""
    from auron_tpu.ops import kernel_cache
    kernel_cache.clear()
    rows = [{"k": i % 10, "v": i} for i in range(300)]
    rows2 = [{"k2": i % 12, "w": i} for i in range(300)]
    _run_join(rows, rows2, "inner", PARTITIONED)
    fams = kernel_cache.family_builds()
    assert fams.get("join.probe_index", 0) >= 1, fams
    assert fams.get("join.range.part", 0) >= 1, fams


# ---------------------------------------------------------------------------
# one-hot group reduce
# ---------------------------------------------------------------------------

@pytest.mark.slow   # PR 18 tier-1 re-split (7.4s; randomized sweep —
#   deterministic onehot-vs-scatter equivalence stays fast)
def test_onehot_reducers_match_scatter_randomized():
    from auron_tpu.ops.hash_group import (
        onehot_segment_extreme, onehot_segment_sum,
    )
    rng = np.random.default_rng(21)
    for trial in range(8):
        n = int(rng.integers(1, 9000))
        g = int(rng.integers(1, 300))
        seg = jnp.asarray(rng.integers(0, g + 2, n).astype(np.int32))
        # ids >= g are out of range: both kernels must drop them
        xf = jnp.asarray(rng.normal(0, 100, n))
        xi = jnp.asarray(rng.integers(-1000, 1000, n).astype(np.int64))
        np.testing.assert_allclose(
            np.asarray(onehot_segment_sum(xf, seg, g)),
            np.asarray(jax.ops.segment_sum(xf, seg, num_segments=g)),
            rtol=1e-12, atol=1e-9)
        np.testing.assert_array_equal(
            np.asarray(onehot_segment_sum(xi, seg, g)),
            np.asarray(jax.ops.segment_sum(xi, seg, num_segments=g)))
        np.testing.assert_array_equal(
            np.asarray(onehot_segment_extreme(xi, seg, g, True)),
            np.asarray(jax.ops.segment_min(xi, seg, num_segments=g)))
        np.testing.assert_array_equal(
            np.asarray(onehot_segment_extreme(xf, seg, g, False)),
            np.asarray(jax.ops.segment_max(xf, seg, num_segments=g)))


def _agg_result(scope):
    rows = [{"k": i % 17, "v": i} for i in range(900)]
    t = pa.Table.from_pylist(rows)
    with conf.scoped(dict(scope)):
        from auron_tpu.ops.agg.exec import AggExec
        a = AggExec(
            MemoryScanExec(from_arrow_schema(t.schema),
                           [Batch.from_arrow(b)
                            for b in t.to_batches(max_chunksize=128)]),
            "single", [col("k")], ["k"],
            [AggExpr(fn="sum", children=(col("v"),),
                     return_type=DataType.int64()),
             AggExpr(fn="min", children=(col("v"),),
                     return_type=DataType.int64()),
             AggExpr(fn="max", children=(col("v"),),
                     return_type=DataType.int64())],
            ["s", "mn", "mx"])
        out = [b.to_arrow()
               for b in a.execute_with_metrics(TaskContext())]
    return sorted(pa.Table.from_batches(out).to_pylist(),
                  key=lambda r: r["k"])


@pytest.mark.slow   # PR 12 tier-1 re-split (7.4s; the randomized
#                     onehot-vs-scatter reducer test stays in tier-1)
def test_agg_forced_onehot_matches_scatter():
    """A real agg plan under the forced one-hot strategy (batch
    capacities here sit under the max.segments ceiling, so the dispatch
    actually fires) equals the scatter run exactly — int aggregates."""
    scatter = _agg_result({"auron.kernel.group.strategy": "scatter"})
    onehot = _agg_result({"auron.kernel.group.strategy": "onehot",
                          "auron.kernel.group.onehot.max.segments": 2048})
    assert scatter == onehot
    assert [r["k"] for r in scatter] == list(range(17))


def test_group_strategy_ceiling_binds_even_when_forced():
    with conf.scoped({"auron.kernel.group.strategy": "onehot",
                      "auron.kernel.group.onehot.max.segments": 64}):
        assert S.group_strategy(64) == "onehot"
        assert S.group_strategy(65) == "scatter"


# ---------------------------------------------------------------------------
# SortExec end-to-end + the spill-merge invariant (ops/sort.py:~220)
# ---------------------------------------------------------------------------

@pytest.fixture
def fresh_memmgr():
    reset_manager()
    yield
    conf.unset("auron.memory.spill.min.trigger.bytes")
    reset_manager()


def _sort_rows(rows, exprs, scope, budget=None, chunk=200, limit=None):
    t = pa.Table.from_pylist(rows)
    if budget:
        conf.set("auron.memory.spill.min.trigger.bytes", 10_000)
        reset_manager(budget_bytes=budget)
    else:
        reset_manager()
    with conf.scoped(dict(scope)):
        s = SortExec(
            MemoryScanExec(from_arrow_schema(t.schema),
                           [Batch.from_arrow(b)
                            for b in t.to_batches(max_chunksize=chunk)]),
            exprs, fetch_limit=limit)
        out = [b.to_arrow()
               for b in s.execute_with_metrics(TaskContext())]
        spills = s.metrics.get("mem_spill_count")
    return pa.Table.from_batches(out).to_pylist(), spills


def test_sort_exec_identical_across_strategies(fresh_memmgr):
    rng = np.random.default_rng(31)
    rows = [{"k": int(rng.integers(-50, 50)) if rng.random() > 0.08
             else None,
             "f": float(rng.normal()), "i": i} for i in range(3000)]
    exprs = [SortExpr(child=col("k"), asc=False, nulls_first=False),
             SortExpr(child=col("f"), asc=True)]
    a, _ = _sort_rows(rows, exprs, ARGSORT)
    b, _ = _sort_rows(rows, exprs,
                      dict(RADIX, **{"auron.kernel.sort.radix.min.rows": 1}))
    assert a == b
    a, _ = _sort_rows(rows, exprs, ARGSORT, limit=37)
    b, _ = _sort_rows(rows, exprs,
                      dict(RADIX, **{"auron.kernel.sort.radix.min.rows": 1}),
                      limit=37)
    assert a == b


def test_sort_spill_merge_identical_under_radix(fresh_memmgr):
    """The ops/sort.py host-side searchsorted spill-merge regression
    (ISSUE 7 satellite): spilled sorted runs must merge identically
    regardless of which in-memory sort strategy produced them, and the
    radix run must actually spill."""
    rng = np.random.default_rng(33)
    n = 6000
    vals = rng.integers(-10**6, 10**6, n)
    rows = [{"k": int(v), "i": i} for i, v in enumerate(vals)]
    exprs = [SortExpr(child=col("k"), asc=True)]
    full, spill_none = _sort_rows(rows, exprs, ARGSORT)
    assert not spill_none
    radix_scope = dict(RADIX, **{"auron.kernel.sort.radix.min.rows": 1})
    spilled_radix, spills_r = _sort_rows(rows, exprs, radix_scope,
                                         budget=60_000, chunk=500)
    spilled_legacy, spills_l = _sort_rows(rows, exprs, ARGSORT,
                                          budget=60_000, chunk=500)
    assert spills_r > 0 and spills_l > 0, "budget must force spills"
    assert spilled_radix == spilled_legacy == full


# ---------------------------------------------------------------------------
# strategy resolution + cost model
# ---------------------------------------------------------------------------

def test_auto_resolutions_on_this_backend():
    # CPU backend: radix above the floor, argsort below; partitioned
    # probe inside its window; scatter group reduce
    assert S.sort_strategy(1 << 20) == "radix"
    assert S.sort_strategy(64) == "argsort"
    assert S.join_probe_strategy(1 << 14) == "partitioned"
    assert S.join_probe_strategy(64) == "searchsorted"
    with conf.scoped({"auron.kernel.join.partitioned.max.rows": 1 << 12}):
        assert S.join_probe_strategy(1 << 14) == "searchsorted"
    assert S.group_strategy(64) == "scatter"


def test_cost_model_seeding(tmp_path):
    m = S.cost_model()
    assert m.argsort_ns > m.packsort_pass_ns > 0
    # profile-file seeding: a recorded artifact overrides the embedded
    # numbers
    prof = tmp_path / "prof.json"
    prof.write_text(
        '{"parsed": {"kernel_profile_ms": {"argsort_u64_ms": 8000.0,'
        '"radix_sort_u64_ms": 1000.0}, "rows": 4194304}}')
    with conf.scoped({"auron.kernel.cost.profile.path": str(prof)}):
        m2 = S.cost_model()
        assert m2.argsort_ns == pytest.approx(8000.0 * 1e6 / 4194304)
        assert m2.packsort_pass_ns == pytest.approx(
            1000.0 * 1e6 / 4194304 / 2)
    with conf.scoped({"auron.kernel.cost.profile.path":
                      str(tmp_path / "missing.json")}):
        assert S.cost_model().argsort_ns == m.argsort_ns


def test_strategy_fingerprint_tracks_knobs():
    base = S.strategy_fingerprint()
    with conf.scoped({"auron.kernel.sort.strategy": "radix"}):
        assert S.strategy_fingerprint() != base
    assert S.strategy_fingerprint() == base


# ---------------------------------------------------------------------------
# bench.py names the device it ran on; only a TPU run may say "/chip"
# ---------------------------------------------------------------------------

def _bench_module():
    import importlib.util
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "bench.py")
    spec = importlib.util.spec_from_file_location("auron_bench", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.mark.parametrize("platform,kind,per_chip", [
    ("tpu", "TPU v5 lite", True), ("cpu", "cpu", False)])
def test_bench_summary_names_its_device(platform, kind, per_chip):
    bench = _bench_module()
    dev = {"platform": platform, "device_kind": kind, "device_count": 1}
    out = bench._summarize(
        {"engine": dict(dev, seconds=2.0, rows=1000),
         "fused": dict(dev, seconds=1.0, rows=1000)}, 100.0, [])
    assert ("/chip" in out["unit"]) is per_chip
    assert {k: out[k] for k in dev} == dev
    # the fused-only summary (engine worker failed) obeys the same rule
    out = bench._summarize({"fused": dict(dev, seconds=1.0, rows=1000)},
                           100.0, ["engine: boom"])
    assert ("/chip" in out["unit"]) is per_chip
    assert out["diagnostics"] == ["engine: boom"]


# ---------------------------------------------------------------------------
# pallas staging kernel parity (interpret mode, like test_pallas_kernels)
# ---------------------------------------------------------------------------

def test_pallas_radix_hist_matches_xla_twin():
    from auron_tpu.ops import kernels_pallas as KP
    rng = np.random.default_rng(17)
    hi = jnp.asarray(rng.integers(0, 1 << 32, 4096).astype(np.uint32))
    got = np.asarray(KP.radix_bucket_hist(hi, 6, interpret=True))
    exp = np.asarray(KP.radix_bucket_hist_xla(hi, 6, tile_rows=32))
    assert got.sum() == 4096
    np.testing.assert_array_equal(got, exp)
    with pytest.raises(ValueError):
        KP.radix_bucket_hist(hi, 12, interpret=True)


# ---------------------------------------------------------------------------
# slow gates: forced-strategy chaos sweep + the kernel_check script
# ---------------------------------------------------------------------------

@pytest.mark.slow
def test_chaos_sweep_bit_identical_with_new_strategies_forced(
        tmp_path_factory):
    """The acceptance criterion: the chaos sweep stays bit-identical
    with every new strategy forced on."""
    from auron_tpu.it.datagen import generate
    from auron_tpu.it.stability import chaos_sweep
    catalog = generate(str(tmp_path_factory.mktemp("ks_tpcds")), sf=0.002,
                       fact_chunks=3)
    spec = ("shuffle.push:io:p=0.2,seed=7;"
            "shuffle.fetch:io:p=0.2,seed=11;"
            "spill.write:io:p=0.2,seed=3")
    with conf.scoped(dict(ALL_NEW)):
        report = chaos_sweep(["q03", "q42"], catalog, spec)
    assert report.ok, report.render()
    assert report.injected_total() > 0, report.render()
    assert all(r.identical for r in report.results), report.render()


@pytest.mark.slow
def test_kernel_check_script():
    """tools/kernel_check.sh is the CI kernel gate (equivalence suite +
    microbench asserting the auto strategy beats or ties the legacy
    kernels); keep it green from tier-1's slow lane like chaos_check/
    mem_check."""
    script = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools", "kernel_check.sh")
    env = dict(os.environ, AURON_KERNEL_CHECK_ROWS=str(1 << 20))
    out = subprocess.run(["bash", script], capture_output=True, text=True,
                         timeout=1200, env=env)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "kernel_check.sh: ok" in out.stdout

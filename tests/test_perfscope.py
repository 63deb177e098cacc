"""perfscope coverage (runtime/perfscope.py): estimator units per
declared kernel family, ledger bounds (signature cap, reservoir ring,
EMA, sampled-call estimates), the /rooflines + Prometheus surfaces, and
the disarmed-default zero-ledger claim the tools/perf_check.sh A/B
rides."""

import json
import os
import subprocess
import sys
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from auron_tpu import config
from auron_tpu.runtime import jitcheck, perfscope


@pytest.fixture(autouse=True)
def _clean_ledger():
    """Every test starts and ends with perfscope DISARMED and an empty
    ledger (conftest arms lockcheck/jitcheck suite-wide but not this —
    arming is per-test, mirroring the OFF-default production contract)."""
    perfscope.reset_state()
    perfscope.configure(False)
    yield
    perfscope.configure(False)
    perfscope.reset_state()


def _arm(**knobs):
    """Arm with the given auron.perf.* knobs: configure() snapshots the
    scoped values into the module globals, which outlive the scope (the
    documented re-arm-to-change contract)."""
    with config.conf.scoped({"auron.perf.enable": True, **knobs}):
        perfscope.configure()


class _FakeLeaf:
    def __init__(self, shape, dtype):
        self.shape = shape
        self.dtype = np.dtype(dtype)


# ---------------------------------------------------------------------------
# estimators
# ---------------------------------------------------------------------------

def test_default_estimator_reads_inputs_once_writes_outputs_once():
    a = _FakeLeaf((1000,), np.float64)      # 8000 B
    b = _FakeLeaf((500, 2), np.int32)       # 4000 B
    out = _FakeLeaf((1000,), np.float32)    # 4000 B
    assert perfscope.default_estimator([a, b], [out]) == 16000


def test_sort_estimator_double_counts_inputs():
    a = _FakeLeaf((1000,), np.uint64)       # 8000 B
    out = _FakeLeaf((1000,), np.int32)      # 4000 B
    fn = perfscope.estimator_for("agg.sort_base")
    assert fn is not perfscope.default_estimator
    assert fn([a], [out]) == 2 * 8000 + 4000
    # the glob form covers the SPMD sort family too
    assert perfscope.estimator_for("spmd.sort_pack") is fn
    # undeclared families fall back to read-once/write-once
    assert perfscope.estimator_for("join.probe_index") \
        is perfscope.default_estimator


def test_declare_estimator_overrides_and_redeclares():
    calls = []

    def custom(ins, outs):
        calls.append(1)
        return 7

    perfscope.declare_estimator("test.fam.*", custom)
    try:
        assert perfscope.estimator_for("test.fam.x")([], []) == 7
        # redeclaration replaces (no duplicate glob entries) and busts
        # the memoized per-site resolution
        perfscope.declare_estimator("test.fam.*", lambda i, o: 9)
        assert perfscope.estimator_for("test.fam.x")([], []) == 9
    finally:
        perfscope.declare_estimator("test.fam.*", perfscope.default_estimator)


# ---------------------------------------------------------------------------
# ledger bounds
# ---------------------------------------------------------------------------

def test_record_totals_and_gbps_identity():
    # 1 GB in 1 s is 1.0 GB/s by the bytes/ns identity
    perfscope.record("unit.site", 1.0, 10 ** 9, signature="s0")
    snap = perfscope.snapshot()["unit.site"]
    assert snap["calls"] == 1
    assert snap["bytes"] == 10 ** 9
    assert abs(snap["gbps"] - 1.0) < 1e-6


def test_untimed_records_count_bytes_and_scale_seconds():
    """seconds=None (the off-stride executions under sampling) add bytes
    and calls; est seconds extrapolates the timed average over ALL
    calls."""
    perfscope.record("unit.sampled", 0.001, 100, signature="s")
    for _ in range(7):
        perfscope.record("unit.sampled", None, 100, signature="s")
    snap = perfscope.snapshot()["unit.sampled"]
    assert snap["calls"] == 8
    assert snap["bytes"] == 800
    # 1ms timed avg x 8 calls = 8ms estimated
    assert abs(snap["seconds"] - 0.008) < 1e-6
    sig = snap["signatures"]["s"]
    assert sig["timed_calls"] == 1 and sig["calls"] == 8


def test_signature_cap_collapses_to_other():
    with config.conf.scoped({"auron.perf.enable": True,
                             "auron.perf.signatures.max": 3}):
        perfscope.configure()
        for i in range(10):
            perfscope.record("unit.cap", 0.001, 10, signature=f"sig{i}")
    led = perfscope.snapshot()["unit.cap"]
    assert len(led["signatures"]) == 4   # 3 distinct + "<other>"
    assert led["signatures"]["<other>"]["calls"] == 7
    assert led["calls"] == 10            # totals never drop samples


def test_reservoir_ring_is_bounded():
    with config.conf.scoped({"auron.perf.enable": True,
                             "auron.perf.reservoir.max": 5}):
        perfscope.configure()
        for i in range(50):
            perfscope.record("unit.ring", 0.001 * (i + 1), 10,
                             signature="s")
    sig = perfscope.snapshot()["unit.ring"]["signatures"]["s"]
    assert sig["samples"] == 5
    assert sig["calls"] == 50


def test_ema_tracks_recent_samples():
    with config.conf.scoped({"auron.perf.enable": True,
                             "auron.perf.ema.alpha": 0.5}):
        perfscope.configure()
        perfscope.record("unit.ema", 0.001, 10, signature="s")  # 1ms
        perfscope.record("unit.ema", 0.003, 10, signature="s")  # 3ms
    sig = perfscope.snapshot()["unit.ema"]["signatures"]["s"]
    # EMA seeds on the first sample then blends: 0.5*3 + 0.5*1 = 2ms
    assert abs(sig["ema_ms"] - 2.0) < 1e-6


# ---------------------------------------------------------------------------
# the shim
# ---------------------------------------------------------------------------

def test_disarmed_shim_records_nothing():
    """The OFF-default claim: a site-built program executed with
    perfscope disarmed leaves a ZERO ledger."""
    fn = jitcheck.site("unit.shim.off").jit(lambda x: x + 1)
    np.testing.assert_array_equal(
        np.asarray(fn(jnp.arange(8))), np.arange(8) + 1)
    assert "unit.shim.off" not in perfscope.snapshot()
    assert perfscope.kernel_seconds() == {}
    assert perfscope.kernel_bytes() == {}


def test_armed_shim_records_site_bytes_and_seconds():
    _arm(**{"auron.perf.sample.stride": 1})
    fn = jitcheck.site("unit.shim.on").jit(lambda x: x * 2)
    x = jnp.arange(1024, dtype=jnp.float32)
    for _ in range(3):
        jax.block_until_ready(fn(x))
    snap = perfscope.snapshot()["unit.shim.on"]
    assert snap["calls"] == 3
    # read-once + write-once: 4KiB in + 4KiB out, per call
    assert snap["bytes"] == 3 * 2 * 4096
    assert snap["seconds"] > 0
    # identical results armed vs disarmed (the shim is observational)
    perfscope.configure(False)
    np.testing.assert_array_equal(np.asarray(fn(x)), np.asarray(x) * 2)


def test_armed_shim_samples_on_stride():
    _arm(**{"auron.perf.sample.stride": 4})
    fn = jitcheck.site("unit.shim.stride").jit(lambda x: x + 1)
    x = jnp.arange(64)
    for _ in range(8):
        jax.block_until_ready(fn(x))
    sig = list(perfscope.snapshot()
               ["unit.shim.stride"]["signatures"].values())[0]
    assert sig["calls"] == 8
    assert sig["timed_calls"] == 2   # calls 0 and 4 of the stride-4 cycle


def test_arming_is_a_runtime_decision():
    """The same program object flips between recorded and unrecorded
    without a rebuild — configure() is live."""
    fn = jitcheck.site("unit.shim.flip").jit(lambda x: x - 1)
    x = jnp.arange(16)
    jax.block_until_ready(fn(x))
    assert "unit.shim.flip" not in perfscope.snapshot()
    _arm(**{"auron.perf.sample.stride": 1})
    jax.block_until_ready(fn(x))
    assert perfscope.snapshot()["unit.shim.flip"]["calls"] == 1
    perfscope.configure(False)
    jax.block_until_ready(fn(x))
    assert perfscope.snapshot()["unit.shim.flip"]["calls"] == 1


def test_shim_skips_outer_traces():
    """A wrapped program called under an outer jit trace must not
    pollute the ledger (avals are symbolic, timing is compile time)."""
    _arm(**{"auron.perf.sample.stride": 1})
    inner = jitcheck.site("unit.shim.traced").jit(lambda x: x * 3)

    outer = jitcheck.site("unit.shim.outer").jit(lambda x: inner(x) + 1)
    jax.block_until_ready(outer(jnp.arange(8)))
    snap = perfscope.snapshot()
    assert "unit.shim.traced" not in snap
    assert snap["unit.shim.outer"]["calls"] == 1


# ---------------------------------------------------------------------------
# machine peak + rooflines
# ---------------------------------------------------------------------------

def test_measure_peak_returns_positive_bandwidth():
    assert perfscope.measure_peak(reps=1) > 0


def test_peak_override_and_cache_file(tmp_path):
    cache = str(tmp_path / "peak.json")
    with config.conf.scoped({"auron.perf.peak.gbps": 123.0}):
        assert perfscope.machine_peak_gbps() == 123.0
    with config.conf.scoped({"auron.perf.peak.path": cache}):
        # no override: probes once, persists the verdict ...
        perfscope._PEAK_CACHE.clear()
        first = perfscope.machine_peak_gbps()
        assert first > 0
        doc = json.load(open(cache))
        assert doc[perfscope._platform()]["gbps"] == first
        # ... and a fresh process-cache read resolves from the file
        perfscope._PEAK_CACHE.clear()
        doc[perfscope._platform()]["gbps"] = 42.5
        json.dump(doc, open(cache, "w"))
        assert perfscope.machine_peak_gbps() == 42.5
    perfscope._PEAK_CACHE.clear()


def test_accelerator_peak_comes_from_the_published_table(monkeypatch):
    """Off the CPU the roofline ceiling is the chip's published HBM
    peak by device_kind, never the host memcpy probe; a device the
    table does not know is an error, not a default."""
    import types

    import jax
    monkeypatch.setattr(perfscope, "_platform", lambda: "tpu")
    monkeypatch.setattr(perfscope, "measure_peak", lambda reps=5: 1 / 0)
    monkeypatch.setattr(jax, "devices", lambda *a: [
        types.SimpleNamespace(device_kind="TPU v5 lite")])
    assert perfscope.machine_peak_gbps() == 819.0
    monkeypatch.setattr(jax, "devices", lambda *a: [
        types.SimpleNamespace(device_kind="TPU v9 imaginary")])
    with pytest.raises(ValueError, match="no published peak"):
        perfscope.machine_peak_gbps()
    # the explicit override still wins
    with config.conf.scoped({"auron.perf.peak.gbps": 5.0}):
        assert perfscope.machine_peak_gbps() == 5.0


def test_rooflines_table_shape():
    perfscope.record("unit.roof", 0.001, 10 ** 6, signature="s")  # 1 GB/s
    with config.conf.scoped({"auron.perf.peak.gbps": 10.0}):
        doc = perfscope.rooflines()
    assert doc["peak_gbps"] == 10.0
    site = doc["sites"]["unit.roof"]
    assert abs(site["achieved_gbps"] - 1.0) < 1e-3
    assert abs(site["gap_ratio"] - 10.0) < 0.1
    assert abs(site["pct_of_peak"] - 10.0) < 0.1
    text = perfscope.render_report(doc)
    assert "unit.roof" in text and "machine peak" in text


def test_render_report_empty_ledger_hint():
    with config.conf.scoped({"auron.perf.peak.gbps": 10.0}):
        text = perfscope.render_report()
    assert "no kernel executions recorded" in text


# ---------------------------------------------------------------------------
# HTTP + Prometheus surfaces
# ---------------------------------------------------------------------------

def _get(url):
    try:
        with urllib.request.urlopen(url, timeout=30) as r:
            return r.status, r.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def test_rooflines_endpoint_and_prometheus_series():
    from auron_tpu.ops import kernel_cache
    from auron_tpu.runtime import profiling
    perfscope.record("unit.http", 0.002, 4 * 10 ** 6, signature="s")
    srv = profiling.ProfilingServer().start()
    try:
        with config.conf.scoped({"auron.perf.peak.gbps": 8.0}):
            code, body = _get(srv.url + "/rooflines")
        assert code == 200
        doc = json.loads(body)
        assert doc["peak_gbps"] == 8.0
        assert doc["sites"]["unit.http"]["calls"] == 1
        assert abs(doc["sites"]["unit.http"]["achieved_gbps"] - 2.0) < 0.01

        code, body = _get(srv.url + "/metrics")
        assert code == 200
        text = body.decode()
        assert 'auron_kernel_seconds{site="unit.http"} 0.002' in text
        assert 'auron_kernel_bytes_total{site="unit.http"} 4000000' in text
        # the family-build labeled series (kernel_cache builds funnel in)
        if kernel_cache.family_builds():
            fam = sorted(kernel_cache.family_builds())[0]
            assert f'auron_kernel_builds_total{{family="{fam}"}}' in text
        else:
            kernel_cache.cached_jit(("unit.prom.fam", 0),
                                    lambda: (lambda x: x))
            code, body = _get(srv.url + "/metrics")
            assert 'auron_kernel_builds_total{family="unit.prom.fam"}' \
                in body.decode()
    finally:
        srv.stop()


def test_metrics_empty_until_armed():
    """Disarmed processes (the default) keep the perfscope series off
    /metrics entirely — no misleading zero-valued series."""
    from auron_tpu.runtime.profiling import _prometheus_text
    assert "auron_kernel_seconds{" not in _prometheus_text()


# ---------------------------------------------------------------------------
# the CI gate script (nightly: drives a real q01 corpus A/B + floors)
# ---------------------------------------------------------------------------

@pytest.mark.slow   # PR 18: ~3min — the full perf_check.sh gate
def test_tools_perf_check_script():
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [os.path.join(repo, "tools", "perf_check.sh")],
        cwd=repo, capture_output=True, text=True, timeout=1800,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode == 0, \
        f"stdout:\n{proc.stdout}\nstderr:\n{proc.stderr}"
    assert "perf_check.sh: ok" in proc.stdout


if __name__ == "__main__":
    sys.exit(pytest.main([__file__, "-v"]))

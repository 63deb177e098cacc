"""Aux-subsystem tests (SURVEY §5): HTTP profiling service endpoints,
structured task logging prefixes, build info, and the config doc
generator."""

import json
import logging
import urllib.request

import pytest

from auron_tpu import config
from auron_tpu.build_info import build_info
from auron_tpu.runtime import profiling, task_logging


def _get(url: str):
    with urllib.request.urlopen(url, timeout=30) as r:
        return r.status, r.read()


def test_profiling_server_endpoints():
    srv = profiling.ProfilingServer().start()
    try:
        # /metrics is Prometheus text by default since the unified
        # export layer; the JSON snapshot moved to ?format=json
        code, body = _get(srv.url + "/metrics")
        assert code == 200
        assert b"auron_tasks_completed_total" in body

        code, body = _get(srv.url + "/metrics?format=json")
        assert code == 200
        m = json.loads(body)
        assert "mem" in m and "counters" in m
        assert "tasks_completed" in m["counters"]

        code, body = _get(srv.url + "/status")
        assert code == 200
        info = json.loads(body)
        assert info["name"] == "auron-tpu" and "jax" in info

        code, body = _get(srv.url + "/debug/pyspy?seconds=0.2")
        assert code == 200 and body  # folded-stacks lines

        code, body = _get(srv.url + "/debug/profile?seconds=0.2")
        assert code == 200 and body[:2] == b"PK"  # zip magic

        # the Spark-UI "Auron tab" analogue: build info + live metrics
        code, body = _get(srv.url + "/auron")
        assert code == 200
        page = body.decode()
        assert "Auron TPU engine" in page and "auron-tpu" in page
    finally:
        srv.stop()


def test_profiling_lazy_start_from_conf():
    assert profiling.maybe_start_from_conf() is None
    with config.conf.scoped({"auron.profiling.http.enable": True}):
        srv = profiling.maybe_start_from_conf()
        assert srv is not None
        # idempotent: same instance on second call
        assert profiling.maybe_start_from_conf() is srv
        srv.stop()


def test_task_counter_increments():
    from auron_tpu.ir import plan as P
    from auron_tpu.ir.schema import DataType, Field, Schema
    from auron_tpu.runtime import counters, executor

    # counters moved to runtime/counters.py — the one registry the
    # executor, /metrics and /queries all share (no more dangling
    # executor._TASKS_* globals read via getattr)
    before_s, before_c = executor.task_attempt_counts()
    plan = P.EmptyPartitions(
        schema=Schema((Field("x", DataType.int64()),)), num_partitions=1)
    executor.execute_plan(plan)
    after_s, after_c = executor.task_attempt_counts()
    assert (after_s, after_c) == (before_s + 1, before_c + 1)
    assert counters.get("tasks_completed") == after_c


def test_task_logging_prefix(caplog):
    log = logging.getLogger("auron_tpu.test")
    f = task_logging.TaskContextFilter()
    rec = logging.LogRecord("auron_tpu.test", logging.INFO, __file__, 1,
                            "hello", (), None)
    f.filter(rec)
    assert rec.task == ""
    with task_logging.task_scope(3, 7):
        assert task_logging.current() == (3, 7)
        f.filter(rec)
        assert rec.task == "[stage 3 part 7] "
    assert task_logging.current() is None


def test_build_info_fields():
    info = build_info()
    assert info["version"] and info["python"]
    assert info["backend"] in ("cpu", "tpu", "gpu")


def test_config_doc_covers_all_options():
    doc = config.conf.generate_doc()
    for opt in config.conf.options():
        assert f"`{opt.key}`" in doc
    # the generated reference in the repo is up to date
    import pathlib
    cfg_md = pathlib.Path(__file__).resolve().parent.parent / "CONFIG.md"
    with open(cfg_md) as f:
        committed = f.read()
    for opt in config.conf.options():
        assert f"`{opt.key}`" in committed, \
            f"CONFIG.md is stale: regenerate with python -m auron_tpu.config"


# -- the compile cache can be placed (config.apply_compile_cache) ----------

@pytest.fixture
def jax_cache_config():
    """Hand the test jax's cache settings and put them back after: a
    directory left set would turn the CPU cache on for the rest of the
    suite (see the note in conftest.py)."""
    import jax
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        yield jax.config
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


def _repo_cache_dir():
    import pathlib
    return str(pathlib.Path(__file__).resolve().parent.parent /
               ".jax_cache")


def test_compile_cache_env_var_stands(monkeypatch, tmp_path,
                                      jax_cache_config):
    """JAX_COMPILATION_CACHE_DIR set: JAX's own handling stands, on any
    backend, whatever explicit path the option names."""
    import jax
    env_dir = str(tmp_path / "from_env")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    # what jax does with the variable when it is imported
    jax_cache_config.update("jax_compilation_cache_dir", env_dir)
    for backend in ("tpu", "cpu"):
        monkeypatch.setattr(jax, "default_backend", lambda b=backend: b)
        assert config.apply_compile_cache() == env_dir
        with config.conf.scoped(
                {"auron.compile.cache.dir": str(tmp_path / "explicit")}):
            assert config.apply_compile_cache() == env_dir
        assert jax_cache_config.jax_compilation_cache_dir == env_dir


def test_compile_cache_defaults_to_the_repo_on_a_device(
        monkeypatch, tmp_path, jax_cache_config):
    import jax
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax_cache_config.update("jax_compilation_cache_dir", None)
    # the CPU stays uncached under 'auto' ...
    assert config.apply_compile_cache() is None
    assert jax_cache_config.jax_compilation_cache_dir is None
    # ... a device backend gets the one fixed directory ...
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert config.apply_compile_cache() == _repo_cache_dir()
    assert jax_cache_config.jax_compilation_cache_dir == _repo_cache_dir()
    assert config.apply_compile_cache() == _repo_cache_dir()  # idempotent
    # ... and an explicit path works while the variable is unset
    explicit = str(tmp_path / "explicit")
    with config.conf.scoped({"auron.compile.cache.dir": explicit}):
        assert config.apply_compile_cache() == explicit
    assert jax_cache_config.jax_compilation_cache_dir == explicit


def test_compile_cache_off_sets_nothing(monkeypatch, jax_cache_config):
    import jax
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    jax_cache_config.update("jax_compilation_cache_dir", None)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for off in ("off", ""):
        with config.conf.scoped({"auron.compile.cache.dir": off}):
            assert config.apply_compile_cache() is None
        assert jax_cache_config.jax_compilation_cache_dir is None


def test_chip_smoke_refuses_a_cpu(tmp_path):
    """No CPU stand-in: without an accelerator the smoke exits non-zero
    before it generates anything and never prints its ok line."""
    import os
    import pathlib
    import subprocess
    import sys
    repo = pathlib.Path(__file__).resolve().parent.parent
    proc = subprocess.run(
        [sys.executable, str(repo / "chip_smoke.py"), "--sf", "0.01",
         "--out", str(tmp_path)],
        cwd=str(tmp_path), capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "needs a TPU" in proc.stderr
    assert not os.listdir(tmp_path)


def test_input_batch_statistics_option():
    """INPUT_BATCH_STATISTICS_ENABLE analogue: per-operator input
    batch/row counters appear in the metric tree when enabled."""
    import numpy as np
    import pyarrow as pa
    from auron_tpu.ir import plan as P
    from auron_tpu.ir.expr import col
    from auron_tpu.ir import expr as E
    from auron_tpu.ir.schema import from_arrow_schema
    from auron_tpu.runtime.executor import execute_plan
    from auron_tpu.runtime.resources import ResourceRegistry

    t = pa.table({"x": np.arange(100, dtype=np.int64)})
    res = ResourceRegistry()
    res.put("t", t.to_batches(max_chunksize=25))
    plan = P.Filter(
        child=P.FFIReader(schema=from_arrow_schema(t.schema),
                          resource_id="t"),
        predicates=(E.BinaryExpr(left=col("x"), op=">",
                                 right=E.Literal(value=10)),))
    with config.conf.scoped({"auron.input.batch.statistics.enable": True}):
        r = execute_plan(plan, resources=res)
    stats = r.metrics.to_dict()
    flat = str(stats)
    assert "input_batch_count" in flat and "input_rows" in flat

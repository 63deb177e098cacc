"""Test bootstrap: force an 8-device virtual CPU mesh.

Multi-chip hardware is unavailable in CI; sharding paths are validated on a
virtual CPU mesh (xla_force_host_platform_device_count=8), mirroring how the
reference exercises distribution via Spark local[*] instead of a cluster
(SURVEY.md §4).

The suite runs on the CPU whatever JAX_PLATFORMS says (the driver sets
it to cpu anyway): the platform is pinned through jax.config.update after
importing jax.  XLA_FLAGS must be set before backend initialization.
"""

import os

xla_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in xla_flags:
    xla_flags = (xla_flags +
                 " --xla_force_host_platform_device_count=8").strip()
os.environ["XLA_FLAGS"] = xla_flags

# concurrency checking is ON for the whole suite (env fallback of
# `auron.lockcheck.enable`) — and it must be set BEFORE auron_tpu is
# imported: the lock factories (runtime/lockcheck.py) decide tracked
# vs raw at CONSTRUCTION time, and module-level locks are constructed
# at import.  Every lock-order cycle, undeclared re-entrant acquire
# and blocking-under-lock the suite exercises raises a structured
# LockcheckError at the offending site instead of deadlocking CI.
os.environ.setdefault("AURON_TPU_AURON_LOCKCHECK_ENABLE", "1")

# compilation-hygiene checking is ON for the whole suite too (env
# fallback of `auron.jitcheck.enable`) — also BEFORE auron_tpu import:
# jit sites decide probed-vs-raw when they WRAP a program, and
# module-level jits wrap at import.  Every retrace storm and
# undeclared implicit device->host transfer the suite exercises raises
# a structured JitcheckError at the offending site.
os.environ.setdefault("AURON_TPU_AURON_JITCHECK_ENABLE", "1")

# wire-protocol conformance checking is ON for the whole suite too (env
# fallback of `auron.wirecheck.enable`) — also BEFORE auron_tpu import:
# the enable flag is decided at process start like lockcheck's.  Every
# malformed frame a test sends or receives on the framed-TCP wires
# raises a structured WirecheckError (client side) or is answered
# in-band (server side) instead of surfacing as a downstream KeyError.
os.environ.setdefault("AURON_TPU_AURON_WIRECHECK_ENABLE", "1")

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

import auron_tpu  # noqa: E402,F401

# verify-before-execute is ON for the whole suite (env fallback of the
# `auron.plan.verify` option): every TaskDefinition any test executes is
# statically checked by auron_tpu.analysis first, so a regression that
# emits a malformed plan fails with node-path diagnostics here even when
# its query would have limped through execution.
os.environ.setdefault("AURON_TPU_AURON_PLAN_VERIFY", "1")

# NOTE on the persistent XLA compilation cache: do NOT enable it here.
# This jaxlib's CPU AOT serialization is unsound — cache WRITES and READS
# of the engine's executables segfault nondeterministically mid-suite
# (observed in jax._src.compilation_cache.put/get_executable_and_time,
# with machine-feature-mismatch warnings on reads).  The suite compiles
# cold instead; per-process jit caches still dedupe within a run.

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.fixture(scope="session", autouse=True)
def _assert_cpu_mesh():
    devs = jax.devices()
    assert devs[0].platform == "cpu", f"tests must run on CPU, got {devs}"
    assert len(devs) == 8, f"expected 8 virtual CPU devices, got {len(devs)}"
    yield


@pytest.fixture
def rng():
    return np.random.default_rng(42)

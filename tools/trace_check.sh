#!/usr/bin/env bash
# CI trace gate (CPU, no accelerator needed):
#   1. run a tier-1 TPC-DS query with tracing ON through the serial
#      path (shuffle/task spans materialize) and a latency fault armed,
#      dumping Chrome-trace JSON (`python -m auron_tpu.trace run`
#      validates the schema before writing)
#   2. re-validate the dumped file through the standalone validator
#   3. run one query with tracing ON through the stage path (the
#      default) and assert the leaf spans under task.execute, spmd.shard
#      and spmd.gather exist, each with the span that caused it
#   4. check the committed EXPLAIN ANALYZE goldens via the pytest hook
#      (tests/test_observability.py; regen with AURON_REGEN_GOLDEN=1)
#
# The same checks run inside the suite (tests/test_observability.py::
# test_tools_trace_check_script, marked slow), mirroring how
# lint_plans.sh / chaos_check.sh are wired.
set -euo pipefail
cd "$(dirname "$0")/.."

out_dir=$(mktemp -d /tmp/auron_trace_check.XXXXXX)
trap 'rm -rf "$out_dir"' EXIT

JAX_PLATFORMS=${JAX_PLATFORMS:-cpu} python -m auron_tpu.trace run \
    --query q01 --sf 0.002 --serial \
    --faults 'shuffle.push:latency:ms=20,max=2,seed=3' \
    -o "$out_dir/q01.trace.json" --analyze

JAX_PLATFORMS=${JAX_PLATFORMS:-cpu} python -m auron_tpu.trace validate \
    "$out_dir/q01.trace.json"

# the compact gather (the accelerators' default) is what splits
# spmd.gather into spmd.wait and spmd.fetch
JAX_PLATFORMS=${JAX_PLATFORMS:-cpu} AURON_TPU_AURON_SPMD_GATHER_COMPACT=on \
    python -m auron_tpu.trace run --query q03 --sf 0.002 \
    -o "$out_dir/q03.stage.trace.json" --analyze

python - "$out_dir/q03.stage.trace.json" <<'PY'
import json
import sys

events = [e for e in json.load(open(sys.argv[1]))["traceEvents"]
          if e["ph"] == "X"]
names = {e["name"] for e in events}
leaves = {"scan.decode", "scan.to_device", "task.to_host", "spmd.tail",
          "shard.pad", "shard.put", "spmd.wait", "spmd.fetch"}
assert leaves <= names, f"stage-path trace lacks {sorted(leaves - names)}"
ids = {e["args"]["id"] for e in events}
assert all(e["args"]["parent"] in ids for e in events
           if e["name"] in leaves), "a leaf span without its parent"
print(f"stage-path trace: {len(events)} spans, every leaf under a parent")
PY

JAX_PLATFORMS=${JAX_PLATFORMS:-cpu} python -m pytest -q \
    -p no:cacheprovider \
    tests/test_observability.py::test_explain_analyze_golden_q03 \
    tests/test_observability.py::test_explain_analyze_fused_fragment_boundary

echo "trace_check.sh: ok"

#!/usr/bin/env bash
# CI perf-regression gate (CPU-only), the ISSUE 19 member of the
# tools/*_check.sh family:
#
#   1. the perfscope unit suite must pass (estimator units, reservoir
#      bounds, the /rooflines + Prometheus surfaces, the disarmed
#      zero-ledger claim);
#   2. the OFF-default claim must hold: an interleaved warm q01 serial
#      A/B with perfscope disarmed vs armed stays bit-identical and the
#      armed overhead stays under AURON_PERF_MAX_OVERHEAD (default 2%);
#   3. achieved per-site bandwidth on a warm q01 run must hold the
#      committed floors in tests/golden_plans/perf_baseline.json within
#      the baseline's tolerance band — a kernel that silently lost an
#      integer factor of bandwidth fails the gate instead of shipping.
#
# Usage: tools/perf_check.sh [--regen-golden]
#   --regen-golden rewrites the floor baseline from this machine's run.
#   AURON_PERF_CHECK_SF shrinks the corpus scale factor (CI boxes).
set -euo pipefail
cd "$(dirname "$0")/.."

SF=${AURON_PERF_CHECK_SF:-0.002}
MAX_OVERHEAD=${AURON_PERF_MAX_OVERHEAD:-0.02}
BASELINE=tests/golden_plans/perf_baseline.json

JAX_PLATFORMS=${JAX_PLATFORMS:-cpu} \
    python -m pytest tests/test_perfscope.py -q -m 'not slow' \
    -p no:cacheprovider -p no:randomly

JAX_PLATFORMS=${JAX_PLATFORMS:-cpu} \
    python -m auron_tpu.perfscope ab --query q01 --sf "$SF" --serial \
    --reps 5 --max-overhead "$MAX_OVERHEAD"

if [[ "${1:-}" == "--regen-golden" ]]; then
    JAX_PLATFORMS=${JAX_PLATFORMS:-cpu} \
        python -m auron_tpu.perfscope check --query q01 --sf "$SF" \
        --serial --baseline "$BASELINE" --regen-golden
else
    JAX_PLATFORMS=${JAX_PLATFORMS:-cpu} \
        python -m auron_tpu.perfscope check --query q01 --sf "$SF" \
        --serial --baseline "$BASELINE"
fi

echo "perf_check.sh: ok"

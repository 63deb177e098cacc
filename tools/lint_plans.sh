#!/usr/bin/env bash
# CI lint gate (fast, no accelerator needed):
#   1. static-analyze every golden plan document in tests/golden_plans
#      (python -m auron_tpu.analysis; exit 2 on any error diagnostic)
#   2. byte-compile the whole tree (syntax-error floor, always available)
#   3. ruff (pyflakes-tier rules, see ruff.toml) when installed — the
#      container image does not bake it in, so it is gated, not required
#
# Regenerate the golden set after intentional plan changes with:
#   python -m auron_tpu.analysis --regen-golden
#
# The same checks run inside the tier-1 suite (tests/test_analysis.py::
# test_golden_corpus_lints_clean and test_tools_lint_script), so CI that
# only runs pytest still gets the gate.
set -euo pipefail
cd "$(dirname "$0")/.."

JAX_PLATFORMS=${JAX_PLATFORMS:-cpu} python -m auron_tpu.analysis --quiet "$@"

python -m compileall -q auron_tpu tests tools

if command -v ruff >/dev/null 2>&1; then
    ruff check auron_tpu tests tools
else
    echo "lint_plans.sh: ruff not installed; plan lint + compileall ran, source lint skipped" >&2
fi
echo "lint_plans.sh: ok"

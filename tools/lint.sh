#!/bin/sh
# Static-analysis gate: repo AST lint + the tiny-config analysis pass suite.
# Error findings in deepspeed_tpu/ fail the run (tests/ findings are
# warn-only); the pytest leg runs every pass against deliberately-broken
# miniature programs (red) and the real engine programs (green), so a
# regression in either the passes or the properties they guard trips CI.
# Wired into tools/fast_tests.sh; also runnable standalone.
cd "$(dirname "$0")/.." || exit 1
echo "== tools/lint.sh: repo AST lint =="
python tools/lint.py deepspeed_tpu tests || exit 1
echo "== tools/lint.sh: analysis pass suite =="
python -m pytest -q tests/unit/analysis -p no:cacheprovider || exit 1

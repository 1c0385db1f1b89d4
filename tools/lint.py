#!/usr/bin/env python
"""Repo AST lint CLI — thin wrapper over ``deepspeed_tpu.analysis.source_lint``.

Usage::

    python tools/lint.py                      # lint deepspeed_tpu + tests
    python tools/lint.py deepspeed_tpu chip_smoke.py --format json
    python tools/lint.py --json               # shorthand for --format json
    python tools/lint.py --rule DS-R011       # only the named rule(s)

Rules (DS-R001 repeat-on-cache through DS-R011 unsharded-pool-placement /
DS-R012 baked-constant-in-jit) are documented in the module and README
("Static analysis"). Findings under ``tests/`` are always
warn-only; error findings anywhere else exit nonzero — that is the CI gate
``tools/lint.sh`` wires into ``tools/fast_tests.sh``. Suppress a deliberate
site with ``# lint: allow(DS-RXXX)`` on the offending line.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from deepspeed_tpu.analysis.source_lint import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main())

"""The harness's own tests: `python -m pytest benchmarks/tests -q` (CPU, ~1 min).
Not part of the repo's tier-1 run, which stays at `tests/`."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
for path in (ROOT, BENCH):
    if path not in sys.path:
        sys.path.insert(0, path)

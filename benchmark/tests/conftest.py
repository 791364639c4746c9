"""Tests of the benchmark's own arithmetic. Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

Not part of the repo's tier-1 (`tests/`), which a benchmark PR may not
touch."""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
DATA = os.path.join(BENCH, "tests", "data")
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

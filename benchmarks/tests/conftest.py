"""Tests of the benchmark's own code. Run by hand from the repo's root:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

They are not part of tier-1 (``tests/``)."""

import os
import sys

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH_DIR)

"""Tests of the benchmark itself, run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q

(the repository's tier-1 suite collects only ``tests/``)."""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(BENCH)
for p in (os.path.join(REPO, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

"""Tests of the benchmark itself, run by hand on the CPU:

    JAX_PLATFORMS=cpu python -m pytest perfbench/tests -q -p no:cacheprovider

They are not tier-1 tests (``tests/`` is) and need no chip."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
PERFBENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(PERFBENCH), PERFBENCH,
          os.path.join(PERFBENCH, "tests")):
    if p not in sys.path:
        sys.path.insert(0, p)

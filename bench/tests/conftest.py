"""The benchmark's tests: ``pytest bench/tests`` (outside the tier-1
suite).  They run on the CPU at the configurations' rehearsal sizes."""
import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
for p in (os.path.join(ROOT, "src"), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

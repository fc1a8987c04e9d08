"""The benchmark's own tests run on the CPU at tiny sizes; the repository's
tier-1 tests (testpaths = tests/) do not collect them.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

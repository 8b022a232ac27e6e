"""Paths for the benchmark's own tests.

Run them with an explicit path, on the CPU:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests

They sit outside the repository's ``testpaths``.  The harness modules are
imported as ``harness.*`` from ``bench/``; the program under test from
``src/``.
"""
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
for p in (BENCH, BENCH.parent / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))

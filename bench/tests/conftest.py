"""The benchmark's self-tests run on the CPU at small sizes:

    JAX_PLATFORMS=cpu python -m pytest -q bench/tests
"""
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
for _p in (ROOT, os.path.join(ROOT, "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

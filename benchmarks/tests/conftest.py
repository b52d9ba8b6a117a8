"""Run by hand: `JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q`.
Tier-1 (`tests/`) does not collect this directory."""
import os
import sys

_BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (_BENCH, os.path.dirname(_BENCH)):
    if p not in sys.path:
        sys.path.insert(0, p)

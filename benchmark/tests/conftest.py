"""Run by hand (`python -m pytest benchmark/tests -q`), on the CPU backend:
not part of tier-1, which collects `tests/` only."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

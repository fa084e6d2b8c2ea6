"""Run by hand: ``python -m pytest benchmarks/tests -q`` from the repo root
(CPU). Not part of the repo's tier-1 suite, which collects ``tests/``."""
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

"""Run by hand, outside tier-1: ``python -m pytest benchmark/tests -q``
from the repo's root, with ``JAX_PLATFORMS=cpu`` (set here if unset:
these tests never touch a chip)."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (ROOT, BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

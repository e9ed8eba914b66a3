"""CPU only. Run with ``JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q``
from the root of the repo (README.md); tier-1's ``pytest tests/`` does not
collect this directory, because the manifest's ``paths`` may hold only
directories that are the benchmark's own."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

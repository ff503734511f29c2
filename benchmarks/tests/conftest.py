"""The benchmark's own tests: CPU only, tiny clusters, the Pallas kernel
interpreted. Run from the repo root:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q
"""

import os
import sys

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
REPO = os.path.dirname(BENCH)
for p in (BENCH, REPO):
    if p not in sys.path:
        sys.path.insert(0, p)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("JAX_ENABLE_X64", "1")

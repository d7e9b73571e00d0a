"""The benchmark's own tests run on the CPU, wherever they are started:
``python3 -m pytest benchmarks/tests -q``. They take nothing from the
repo's ``tests/``."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.dirname(BENCH), BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

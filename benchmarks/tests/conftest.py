"""Tests of the benchmark's own yardstick. Run by hand:

    JAX_PLATFORMS=cpu python -m pytest benchmarks/tests -q

Not part of tier-1, which collects ``tests/`` only.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

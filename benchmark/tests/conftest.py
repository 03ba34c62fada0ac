"""The benchmark's own checks: run by hand, on the CPU, no chip needed.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q

They are no part of ``tests/`` and of tier-1."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

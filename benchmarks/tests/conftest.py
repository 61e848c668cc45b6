"""The harness's own tests: ``JAX_PLATFORMS=cpu python -m pytest
benchmarks/tests -q`` from the checkout's root.  They need no chip."""

import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

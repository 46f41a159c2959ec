"""CPU tests of the benchmark harness: ``python -m pytest bench/tests``.

JAX is held to the CPU here; the chip's own runs are ``bench/run.py``.
"""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

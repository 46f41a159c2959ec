"""Order statistics shared by the end-to-end and per-layer metrics."""

from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """The q-th percentile, interpolated linearly between order
    statistics (numpy's default)."""
    return float(np.percentile(np.asarray(values, dtype=float), q))

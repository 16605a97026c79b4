"""Percentiles, as ``numpy.percentile`` takes them (linear between the
two nearest ranks; the arithmetic of the program's jitter statistics)."""
from __future__ import annotations

import numpy as np


def percentile(samples, q: float) -> float | None:
    x = np.asarray(samples, dtype=np.float64)
    if x.size == 0:
        return None
    return float(np.percentile(x, q))

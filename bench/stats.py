"""Percentiles, copied from ``serving/metrics.py`` so that the yardstick
stays with the benchmark."""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def percentile(xs: Sequence[float], q: float) -> Optional[float]:
    """Linear-interpolated percentile, or ``None`` when there are no
    samples ("not measured" is not zero)."""
    if not len(xs):
        return None
    return float(np.percentile(np.asarray(xs, np.float64), q))

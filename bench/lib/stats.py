"""Percentiles, the one way every metric takes them."""

from __future__ import annotations

from typing import Optional, Sequence

import numpy as np


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The ``q``-th percentile, interpolating linearly between order
    statistics (numpy's default); None for no values."""
    if not len(values):
        return None
    return float(np.percentile(np.asarray(values, dtype=np.float64), q))

"""Arithmetic the metric readers share."""
from __future__ import annotations

import math
from typing import List, Optional


def percentile(values: List[float], p: float) -> Optional[float]:
    """Nearest-rank percentile over every value, none left out."""
    if not values:
        return None
    v = sorted(values)
    return v[max(0, math.ceil(p / 100.0 * len(v)) - 1)]

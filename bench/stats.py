"""Nearest-rank percentiles over all samples (the gateway's arithmetic)."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], pct: float) -> float:
    """Nearest-rank percentile of `values` (any order); `inf` entries count
    as samples that missed. Raises on an empty sequence."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no samples")
    i = min(len(vals) - 1, max(0, math.ceil(pct / 100.0 * len(vals)) - 1))
    return float(vals[i])

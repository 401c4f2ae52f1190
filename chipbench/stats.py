"""The percentile the serving metrics use, kept with the benchmark."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Nearest rank: the smallest value with at least a ``q`` share of the
    sample at or below it (rank ``ceil(q n)``)."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of no values")
    rank = math.ceil(q * len(vals))
    return vals[min(len(vals) - 1, max(0, rank - 1))]


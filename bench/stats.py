"""Order statistics for the benchmark's latencies."""
from __future__ import annotations

from typing import Sequence


def percentile(values: Sequence[float], q: float, default: float = 0.0) -> float:
    """Nearest-rank percentile of raw samples, ``q`` in [0, 1] (the rule of
    the program's ``obs.metrics.percentile``, copied so that no program
    change can move it)."""
    if not 0.0 <= q <= 1.0:
        raise ValueError(f"q must be in [0, 1], got {q}")
    vals = sorted(values)
    if not vals:
        return default
    return float(vals[int(round(q * (len(vals) - 1)))])


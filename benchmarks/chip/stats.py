"""Percentile arithmetic, copied from the program's ``workloads/metrics.py``
(nearest-rank, no interpolation) so that a change to the program cannot move
the yardstick."""
from __future__ import annotations

from typing import List


def percentile(sorted_vals: List[float], q: float) -> float:
    """Nearest-rank percentile of an ascending-sorted list."""
    if not sorted_vals:
        return float("nan")
    rank = max(1, -(-int(q * len(sorted_vals)) // 100))  # ceil(q*n/100), >= 1
    return sorted_vals[min(rank, len(sorted_vals)) - 1]


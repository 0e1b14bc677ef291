"""Arithmetic the metrics share."""
from __future__ import annotations

import math


def percentile(values, p: float):
    """Nearest-rank ``p``-th percentile (the smallest value with at least
    ``p`` percent of the values at or below it); None for no values."""
    if not values:
        return None
    v = sorted(values)
    return v[max(math.ceil(p / 100.0 * len(v)) - 1, 0)]


def thompson_bytes(queries: int, cohorts: int, chunks: int) -> int:
    """Least bytes one Thompson choice over ``[Q, C, M]`` moves: the
    statistics alpha and beta ``[Q, M]`` (float32) read once and the
    ``[Q, C]`` winners (int32) written.  The normals are left out: a
    kernel that draws them on the chip reads none."""
    return 4 * (2 * queries * chunks + queries * cohorts)


def thompson_flops(queries: int, cohorts: int, chunks: int) -> int:
    """Operations of the Wilson-Hilferty transform and the argmax over
    ``[Q, C, M]``: 11 per score and one compare."""
    return 12 * queries * cohorts * chunks

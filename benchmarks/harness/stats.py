"""Percentiles over every reading: no reservoir, no subsampling."""

from __future__ import annotations

import math
from typing import Sequence


def percentile(values: Sequence[float], q: float) -> float:
    """Linear-interpolated percentile ``q`` in [0, 100] of ``values``."""
    if not values:
        raise ValueError("percentile of no readings")
    xs = sorted(values)
    pos = (len(xs) - 1) * q / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> float:
    return percentile(values, 50.0)


def worst_leaf_gap(program, reference):
    """The widest gap between the program's norm of a leaf and the
    reference's, against the reference's norm of that leaf or of the median
    leaf, whichever is larger."""
    mid = median(list(reference.values()))
    worst, where = 0.0, ""
    for name, ref in reference.items():
        gap = abs(program[name] - ref) / max(ref, mid, 1e-30)
        if gap > worst:
            worst, where = gap, name
    return worst, where

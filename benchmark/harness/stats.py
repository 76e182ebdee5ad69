"""Medians and percentiles over the readings of one run."""

from __future__ import annotations

import math
from typing import Sequence


class TooFewSamples(ValueError):
    """A percentile was asked of a sample that cannot carry it."""


def median(values: Sequence[float]) -> float:
    if not values:
        raise TooFewSamples("median of no samples")
    s = sorted(values)
    mid = len(s) // 2
    return float(s[mid]) if len(s) % 2 else (s[mid - 1] + s[mid]) / 2.0


def percentile(values: Sequence[float], q: float,
               beyond: int = 10) -> float:
    """The q-th percentile (0 < q < 100), nearest rank from above.

    Refuses a sample with fewer than `beyond` readings above the rank it
    returns: a 95th percentile over a dozen requests is a maximum, and a
    maximum is not what the name says."""
    if not 0.0 < q < 100.0:
        raise ValueError(f"percentile {q} outside (0, 100)")
    n = len(values)
    rank = max(math.ceil(n * q / 100.0), 1)  # 1-based
    if n - rank < beyond:
        raise TooFewSamples(
            f"p{q:g} of {n} samples leaves {max(n - rank, 0)} beyond it "
            f"(need {beyond})")
    return float(sorted(values)[rank - 1])

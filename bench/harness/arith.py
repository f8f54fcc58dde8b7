"""Metric arithmetic, kept with the benchmark so every PR computes a
number the same way."""

from __future__ import annotations

import statistics
from typing import Sequence


def median(values: Sequence[float]) -> float:
    return float(statistics.median(values))


def rate(work: float, seconds: float):
    """Work per second; undefined (``None``) without elapsed time."""
    return work / seconds if seconds > 0 else None

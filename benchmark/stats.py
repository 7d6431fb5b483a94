"""Order statistics shared by the readers."""

from __future__ import annotations

import math


def pct(values, p: float):
    """Nearest-rank percentile (``p`` in 0..1): the smallest value with
    at least ``ceil(p * n)`` observations at or below it; None on no
    values. The arithmetic of ``scripts/serve_bench.py:_pct``
    (``obs/telemetry.py:nearest_rank_percentile``), copied."""
    vals = sorted(values)
    if not vals:
        return None
    return vals[min(len(vals) - 1, max(0, math.ceil(p * len(vals)) - 1))]


def median(values):
    return pct(values, 0.5)


def mean(values):
    vals = list(values)
    return sum(vals) / len(vals) if vals else None

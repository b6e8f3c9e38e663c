"""Summaries of repeated measurements.

``percentiles`` is the linear-interpolation percentile the repo's CPU
benchmarks use (``benchmarks/stats.py``), copied so the yardstick
cannot change under a later PR.  ``spread`` is the quartile spread the
bounds in ``BENCHMARK.json`` are set from.
"""

from __future__ import annotations

import statistics

import numpy as np

__all__ = ["percentiles", "spread"]


def percentiles(samples, qs=(50, 95)) -> dict:
    """{"p50": ..., "p95": ...} by linear interpolation over the sample."""
    arr = np.asarray(list(samples), dtype=float)
    if arr.size == 0:
        return {f"p{int(q)}": float("nan") for q in qs}
    return {f"p{int(q)}": float(np.percentile(arr, q)) for q in qs}


def spread(values) -> float:
    """Distance between the first and third quartile as a share of the
    median, with Python's ``statistics.quantiles(values, n=4)``."""
    q1, med, q3 = statistics.quantiles(list(values), n=4)
    return (q3 - q1) / med

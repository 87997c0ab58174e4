"""The benchmark's arithmetic over a whole measured window.

Percentiles are numpy's linear interpolation (as ``benchmarks/fig6_dnn_cdf``
takes them), over every sample: no statistic is a median of chunks.
Response times follow the RT job's releases, which fall exactly at
``k * period`` from the start of the window.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import numpy as np


def percentile(xs: Sequence[float], q: float) -> float:
    if len(xs) == 0:
        raise ValueError("percentile of no samples")
    return float(np.percentile(np.asarray(xs, np.float64), q))


def releases_due(period: float, window: float) -> int:
    """Releases at 0, period, 2*period, ... strictly before the close."""
    return int(math.ceil(window / period - 1e-9))


def release_responses(period: float, window: float,
                      finished: Sequence[float]) -> List[float]:
    """Response time of every release due in the window, in release
    order. ``finished[k]`` is release k's response (finish - release) for
    the releases the job finished, in release order; a release that had
    not finished when the window closed, or finished after the close,
    counts with the time it had waited by the close."""
    n = releases_due(period, window)
    out = []
    for k in range(n):
        waited = window - k * period
        out.append(min(finished[k], waited) if k < len(finished) else waited)
    return out


def deadline_met(period: float, window: float,
                 finished: Sequence[float]) -> Dict[str, float]:
    """Share of the releases whose implicit deadline (release + period)
    fell inside the window that finished by it. Unfinished releases count
    as missed."""
    n = int(math.floor(window / period + 1e-9))
    met = sum(1 for k in range(min(n, len(finished)))
              if finished[k] <= period + 1e-12)
    return {"due": n, "met": met, "share": met / n if n else float("nan")}


def rate(count: int, window: float) -> float:
    return count / window


"""Percentile and rate arithmetic of the end-to-end metrics.

Percentiles interpolate linearly between order statistics (numpy's
default ``linear`` method).  All times are host seconds from
``time.monotonic``, taken by the client.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence

import numpy as np

def pct(xs: Sequence[float], q: float) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), q)) if len(xs) else math.nan


def ttft(calls, end: float) -> List[float]:
    """Due time to first delivered token; a call with no token by
    ``end`` reads ``end - due`` (a lower bound)."""
    return [(c.stamps[0] if c.stamps else end) - c.due for c in calls]


def itl_ms(calls) -> List[float]:
    """Every gap between consecutive delivered tokens of the calls, in
    milliseconds."""
    return [1e3 * g for c in calls for g in np.diff(np.asarray(c.stamps, np.float64))]


def workflow_e2e(flows, done: Dict[int, float], t0: float, end: float) -> List[float]:
    """Scheduled arrival to the last stage's finish; an unfinished
    workflow reads ``end - arrival`` (a lower bound)."""
    return [done.get(f.wid, end) - (t0 + f.arrival) for f in flows]


def rate(n: int, seconds: float) -> float:
    return n / seconds


def summary(name: str, xs: Sequence[float], qs=(50, 75, 90)) -> str:
    """Percentiles with the number of samples beyond each."""
    parts = [f"{name}: n={len(xs)}"]
    for q in qs:
        beyond = int(round(len(xs) * (1 - q / 100.0)))
        parts.append(f"p{q}={pct(xs, q):.6g} ({beyond} beyond)")
    return " ".join(parts)

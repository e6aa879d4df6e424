"""Arithmetic the metric readers share: latencies from the due time, and
percentiles by nearest rank."""
from __future__ import annotations

import math

import numpy as np


def rank(values, q: float) -> float:
    """The ``q``-th percentile by nearest rank: the smallest value with at
    least ``q`` percent of the values at or below it."""
    v = np.sort(np.asarray(values, dtype=np.float64))
    if not len(v):
        return math.nan
    return float(v[max(0, math.ceil(q / 100.0 * len(v)) - 1)])


def latencies_s(window) -> np.ndarray:
    """Seconds from each request's due time to its completion, for every
    request due in the window. One never served counts until the drain
    ended, which misses every limit a served request could meet."""
    done = np.where(np.isnan(window.done), window.ended, window.done)
    return done - window.due


def traced_calls(run):
    """(call, device seconds) of the engine calls whose prefill and fused
    decode both ran inside the traced window."""
    if run.trace is None:
        return []
    return [(run.window.calls[i], rec) for i, rec in run.trace["calls"].items()
            if rec["prefill"] > 0 and rec["fused_decode"] > 0]

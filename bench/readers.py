"""Helpers that the metric readers in ``bench/metrics`` share."""
from __future__ import annotations

import math

import numpy as np


def percentile(values, p: float) -> float | None:
    """The p-th percentile, taken as a value that occurs (no interpolation,
    so an infinite latency stays infinite)."""
    values = list(values)
    if not values:
        return None
    return float(np.percentile(np.asarray(values, np.float64), p,
                               method="higher"))


def layer_s_per_problem(m, layer: str) -> float | None:
    """Device seconds of a layer per problem of the traced window."""
    if m.trace is None or not m.records:
        return None
    total = m.trace["layers"].get(layer, 0.0)
    return total / len(m.records) if total > 0 else None


def roofline_pct(m, layer: str) -> float | None:
    """The layer's least time per problem (``bench.work``) over its device
    time per problem, in percent."""
    secs = layer_s_per_problem(m, layer)
    if secs is None or layer not in m.work or not m.peak:
        return None
    return 100.0 * m.work[layer].least_s(m.peak) / secs


def idle_pct(m) -> float | None:
    if m.trace is None or m.trace["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - m.trace["busy_s"] / m.trace["window_s"])


def latencies(m) -> list:
    """Completion less due time of every request; a failed one is
    infinite."""
    return [r.done_s - r.request.due_s if not r.error and r.result is not None
            else math.inf for r in m.records]

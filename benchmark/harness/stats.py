"""Percentiles and the per-request gap. Jax-free."""

from __future__ import annotations

import math


def percentile(values, q: float) -> float:
    """The q-th percentile (0..100) with linear interpolation between the
    closest ranks (numpy's default). Raises on an empty sample: a metric
    with nothing under it is left out, not reported as 0."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of an empty sample")
    k = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(k), math.ceil(k)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


def mean_gap_s(first_s: float, last_s: float, tokens: int):
    """Mean gap between the tokens of one request: (last - first) /
    (tokens - 1). Tokens arrive `decode_chunk` at a time, so the mean over
    the request is what a reader of the stream feels. None under 2 tokens."""
    if tokens < 2:
        return None
    return (last_s - first_s) / (tokens - 1)

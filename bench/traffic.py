"""Load generation and latency arithmetic, shared by every serve cell.

Arrival schedules are drawn from the seed with a fixed count: ``n =
round(rate * seconds)`` requests, so every seed offers the same amount of
work in another order.  Given its count, a Poisson process on [0, T] has
uniform order statistics as arrival times, which normalised cumulative
exponential gaps give exactly.  Request sizes are likewise a fixed share of
each size in a seed-drawn order.
"""
from __future__ import annotations

import math

import numpy as np


def poisson_schedule(rate_rps: float, seconds: float, rng) -> np.ndarray:
    """Send times (s from the window start) of a Poisson process of rate
    ``rate_rps`` conditioned on ``round(rate * seconds)`` arrivals."""
    n = int(round(rate_rps * seconds))
    gaps = rng.exponential(1.0, n + 1)
    return np.cumsum(gaps)[:n] * (seconds / gaps.sum())


def request_sizes(params: dict, n: int, rng) -> np.ndarray:
    """Images asked for by each of ``n`` requests: ``size_min`` to
    ``size_max`` in turn, so every seed sends the same sizes, shuffled by
    the seed."""
    lo, hi = params["size_min"], params["size_max"]
    return rng.permutation(lo + np.arange(n) % (hi - lo + 1))


def schedule(params: dict, seconds: float, rng) -> np.ndarray:
    """Send times of an open-loop cell's requests."""
    if params["arrivals"] != "poisson":
        raise ValueError(f"no open-loop arrivals {params['arrivals']!r}")
    return poisson_schedule(params["rate_rps"], seconds, rng)


def latencies_ms(due_s, done_s) -> list[float]:
    """Latency of each request from when it was due to be sent to when its
    result was in hand; a request with no result (None) is infinitely late."""
    return [math.inf if d is None else (d - s) * 1e3 for s, d in zip(due_s, done_s)]


def percentile(xs, p: float) -> float:
    """Linear-interpolated percentile (p in [0, 100]); an infinite value
    propagates to any percentile that interpolates toward it."""
    s = sorted(xs)
    if not s:
        raise ValueError("percentile of an empty list")
    rank = (p / 100.0) * (len(s) - 1)
    lo = int(rank)
    hi = min(lo + 1, len(s) - 1)
    frac = rank - lo
    if math.isinf(s[lo]) or (frac > 0 and math.isinf(s[hi])):
        return math.inf
    return s[lo] * (1 - frac) + s[hi] * frac

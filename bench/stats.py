"""Percentiles used by every cell."""
from __future__ import annotations

import math


def nearest_rank(values, q: float) -> float:
    """The q-quantile (0 < q <= 1) by nearest rank: the ceil(q*n)-th
    smallest value. Every reported percentile is one of the values."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no values")
    k = max(1, math.ceil(q * len(xs)))
    return float(xs[k - 1])


def ttft_ms(requests, t_end: float) -> list:
    """First token minus due time, per request, in ms. A request with no
    first token by ``t_end`` counts with what it had waited by then."""
    out = []
    for r in requests:
        t = r["t_first"] if r["t_first"] is not None else t_end
        out.append((t - r["due"]) * 1e3)
    return out


def itl_ms(requests) -> list:
    """Every gap between successive tokens of every request, in ms."""
    out = []
    for r in requests:
        ts = r["tok_times"]
        out.extend((b - a) * 1e3 for a, b in zip(ts, ts[1:]))
    return out

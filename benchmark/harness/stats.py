"""Arithmetic on samples: percentiles with their counts, and the two
serving times taken from a request's own timestamps."""

from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> tuple[float, int]:
    """``(value, n)``: the q-th percentile (0..100) of ``values`` by
    linear interpolation between order statistics, and the sample count
    it was taken from. No sample is an error, not a zero."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of no samples")
    if not 0 <= q <= 100:
        raise ValueError(f"percentile {q} outside 0..100")
    pos = (len(xs) - 1) * q / 100.0
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo), len(xs)


def iqr_share(values: Sequence[float]) -> float:
    """The spread the bounds are set from: the distance between the
    first and the third quartile as ``statistics.quantiles(n=4)`` gives
    them, as a share of the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def ttft_ms(due_s: float, first_token_s: Optional[float]) -> float:
    """Time to first token from when the request was DUE, not from when
    the generator got round to sending it; no first token is infinite
    (a failed or refused request counts as the worst)."""
    if first_token_s is None:
        return math.inf
    return (first_token_s - due_s) * 1e3


def tpot_ms(token_times_s: Sequence[float]) -> Optional[float]:
    """Time per output token of one request: (last − first) / (n − 1).
    Counts every stall in proportion whatever the engine's burst size.
    A request of one token has none."""
    if len(token_times_s) < 2:
        return None
    return (
        (token_times_s[-1] - token_times_s[0])
        / (len(token_times_s) - 1)
        * 1e3
    )


def max_gap_ms(token_times_s: Sequence[float]) -> Optional[float]:
    if len(token_times_s) < 2:
        return None
    return max(
        b - a for a, b in zip(token_times_s, token_times_s[1:])
    ) * 1e3

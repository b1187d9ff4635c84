"""Latency and rate arithmetic over one run's request records.

All times are seconds on the host's clock.  A record holds when its
request was due, and when the host received each of its tokens.
"""
from __future__ import annotations

import math
from typing import Iterable, List, Optional


def percentile(values: Iterable[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least q% of
    the sample at or below it."""
    s = sorted(values)
    if not s:
        raise ValueError("percentile of an empty sample")
    rank = max(1, math.ceil(q / 100.0 * len(s)))
    return s[rank - 1]


def ttft_samples(records, w0: float, w1: float, t_end: float) -> List[float]:
    """Time to first token of every request due in [w0, w1), from its due
    time.  A request with no first token counts at its elapsed time at
    `t_end`, the moment the run stopped waiting for it."""
    out = []
    for r in records:
        if r.due is None or not w0 <= r.due < w1:
            continue
        first = r.times[0] if r.times else t_end
        out.append(first - r.due)
    return out


def itl_samples(records, w0: float, w1: float) -> List[float]:
    """Every gap between consecutive tokens of a request that ends in
    [w0, w1)."""
    out = []
    for r in records:
        t = r.times
        out.extend(b - a for a, b in zip(t, t[1:]) if w0 <= b < w1)
    return out


def tokens_in(records, w0: float, w1: float) -> int:
    """Tokens the host received in [w0, w1)."""
    return sum(1 for r in records for t in r.times if w0 <= t < w1)


def output_tok_s(records, w0: float, w1: float) -> float:
    return tokens_in(records, w0, w1) / (w1 - w0)


def p95_ms(samples: List[float]) -> Optional[float]:
    return 1e3 * percentile(samples, 95) if samples else None

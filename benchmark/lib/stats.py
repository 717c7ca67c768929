"""Percentiles and rates over the benchmark's own samples.

Exact order statistics over every sample (``observability/slo.py``'s
log-bucket histogram is good to ~19% and is not used here).  The rate of a
training cell is the whole-step rate: all the tokens of N whole steps over
all the time from the first step's start to the last step's end, so a stall
between or inside steps counts and no step is ever counted or not counted
at the edge of a fixed window.
"""
from __future__ import annotations

import math
import statistics
from typing import Optional, Sequence


def percentile(values: Sequence[float], q: float) -> Optional[float]:
    """The q-quantile (0..1) with linear interpolation between order
    statistics (``tools/serve_bench.py``'s ``_pct``); None when empty."""
    if not values:
        return None
    xs = sorted(values)
    pos = q * (len(xs) - 1)
    lo, hi = math.floor(pos), math.ceil(pos)
    return xs[lo] + (xs[hi] - xs[lo]) * (pos - lo)


def median(values: Sequence[float]) -> Optional[float]:
    return statistics.median(values) if values else None


def whole_step_rate(n_steps: int, tokens_per_step: int, elapsed_s: float,
                    chips: int) -> float:
    """tokens/s/chip of ``n_steps`` whole steps over ``elapsed_s``, the time
    from the first step's start to the last step's end: all the work over
    all the time, gaps between steps included.  The judged rate."""
    return tokens_per_step * n_steps / elapsed_s / chips


def median_step_rate(step_s: Sequence[float], tokens_per_step: int,
                     chips: int) -> float:
    """tokens/s/chip from the median step time.  One stall moves it by
    nothing, so it is never judged: it stands beside the whole-step rate
    (``step_ms_p50.train``, ``train_mfu``) to say what a steady step costs."""
    return tokens_per_step / statistics.median(step_s) / chips


def spread(values: Sequence[float]) -> float:
    """Interquartile distance as a share of the median — the contract's
    measure of run-to-run noise."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)

"""Seeded open-loop arrivals and request sizes.

The arrival arithmetic is ``tools/serve_bench.py``'s ``build_stream``
(exponential gaps at a fixed rate).  What differs: the *work* of a run is
fixed by the traffic file, not by ``--seed``.  The arrival times and the
(prompt, output) size of each arrival come from seeds written in the
traffic file; ``--seed`` draws token contents and weights in the caller.
Every seed therefore offers the same request sizes at the same instants.
Dealing the sizes onto the arrivals in another order per seed was tried in
simulation (PERF.md, PR 23): it is a different queueing problem per seed and
moved tokens-in-window by 7% and the TTFT tail by 8-50%.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np


def poisson_arrivals(rate_per_s: float, seconds: float,
                     arrival_seed: int) -> np.ndarray:
    """Arrival offsets in [0, seconds): cumulative exponential gaps."""
    if rate_per_s <= 0 or seconds <= 0:
        raise ValueError(f"rate {rate_per_s} and seconds {seconds} must be > 0")
    rng = np.random.default_rng(arrival_seed)
    out: List[float] = []
    t = float(rng.exponential(1.0 / rate_per_s))
    while t < seconds:
        out.append(t)
        t += float(rng.exponential(1.0 / rate_per_s))
    return np.asarray(out)


def draw_lengths(spec: Dict, n: int, rng: np.random.Generator) -> np.ndarray:
    """``{"dist": "lognormal", "median", "sigma", "min", "max"}`` or
    ``{"dist": "uniform", "min", "max"}`` (inclusive), as whole tokens."""
    lo, hi = int(spec["min"]), int(spec["max"])
    if spec["dist"] == "uniform":
        return rng.integers(lo, hi + 1, n)
    if spec["dist"] == "lognormal":
        x = rng.lognormal(np.log(spec["median"]), spec["sigma"], n)
        return np.clip(np.rint(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown length distribution {spec['dist']!r}")


def open_loop_schedule(traffic: Dict, seconds: float
                       ) -> List[Tuple[float, int, int]]:
    """[(arrival_s, prompt_tokens, output_tokens)] in arrival order."""
    arrivals = poisson_arrivals(traffic["rate_per_s"], seconds,
                                traffic["arrival_seed"])
    sizes = np.random.default_rng(traffic["size_seed"])
    prompts = draw_lengths(traffic["prompt_tokens"], len(arrivals), sizes)
    outputs = draw_lengths(traffic["output_tokens"], len(arrivals), sizes)
    return [(float(t), int(p), int(o))
            for t, p, o in zip(arrivals, prompts, outputs)]

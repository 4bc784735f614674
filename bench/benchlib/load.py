"""The general load generator: arrival schedules read from a traffic mix.

A traffic mix is data (``bench/traffic/<name>.json``).  For an open loop
it gives ``arrivals`` and a fixed offered ``rate_per_s``; the schedule is a
pure function of the mix, the seed and the window length:

* ``poisson``: a Poisson process conditioned on its count, i.e.
  ``round(rate * seconds)`` arrival times drawn uniformly over the window
  and sorted.  Every seed offers the same number of requests, in another
  order, so seeds differ in arrangement and not in amount of work.

Offsets are seconds from the start of the window.
"""
from __future__ import annotations

import numpy as np

ARRIVALS = ("poisson",)


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent numpy stream per (seed, purpose)."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


def arrival_offsets(traffic: dict, seed: int, seconds: float) -> np.ndarray:
    kind = traffic["arrivals"]
    if kind not in ARRIVALS:
        raise ValueError(f"unknown arrivals {kind!r}; options: {ARRIVALS}")
    n = max(int(round(float(traffic["rate_per_s"]) * seconds)), 1)
    return np.sort(rng(seed, 1).uniform(0.0, seconds, n))

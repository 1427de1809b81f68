"""Open-loop Poisson arrivals at a fixed rate.

Every seed gets the same set of gaps in another order: the gaps are the
exponential distribution's quantiles at (k + 1/2) / n for the n = rate x
seconds events of the window, shuffled by the seed and scaled to fill the
window exactly. So the count and the spread of the gaps never change with
the seed; only the order of the arrivals does. The inverse transform is
that of ``repro.serve.workload.arrival_times`` (an exponential variate).
"""
from __future__ import annotations

import numpy as np

OPEN_LOOP = True


def schedule(params: dict, seconds: float,
             rng: np.random.Generator) -> np.ndarray:
    """Arrival offsets in seconds from the window's start, ascending."""
    n = max(1, int(round(params["rate_eps"] * seconds)))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n)
    gaps = rng.permutation(gaps)
    gaps *= seconds / gaps.sum()
    return np.concatenate([[0.0], np.cumsum(gaps[:-1])])

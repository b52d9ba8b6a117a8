"""Open-loop Poisson arrivals at `rate_per_s`: the stratified quantiles of
the exponential gap, in an order from `rng`, scaled so that the last gap
ends with the window. Every seed gets the same multiset of gaps."""
import numpy as np

from traffic import quantiles


def due(spec: dict, seconds: float, rng) -> np.ndarray:
    n = max(int(round(spec["rate_per_s"] * seconds)), 1)
    gaps = -np.log1p(-quantiles(n))[rng.permutation(n)]
    return (np.cumsum(gaps) - gaps) * (seconds / gaps.sum())

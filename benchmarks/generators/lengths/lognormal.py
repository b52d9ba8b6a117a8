"""Log-normal lengths (`median`, `sigma`), clipped to `min` and `max`: the
stratified quantiles, so every seed gets the same multiset."""
import statistics

import numpy as np

from traffic import quantiles


def lengths(spec: dict, n: int) -> np.ndarray:
    nd = statistics.NormalDist()
    z = np.array([nd.inv_cdf(q) for q in quantiles(n)])
    x = spec["median"] * np.exp(spec["sigma"] * z)
    return np.clip(np.rint(x), spec["min"], spec["max"]).astype(np.int64)

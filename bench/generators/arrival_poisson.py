"""Poisson arrivals (``"process": "poisson"``) at ``rate_per_s``.

For ``n = ceil(rate x seconds)`` requests the gaps are the ``n``
stratified quantiles of the exponential distribution at that rate, in the
order the ``order`` generator shuffles them; the first request arrives at
0, so the last lands near, not at, the end.
"""
import math

import numpy as np

from bench.loadgen import strata


def times(params: dict, seconds: float, order) -> np.ndarray:
    rate = params["rate_per_s"]
    n = max(1, math.ceil(rate * seconds))
    gaps = order.permutation(-np.log1p(-strata(n)) / rate)
    return np.cumsum(gaps) - gaps[0]

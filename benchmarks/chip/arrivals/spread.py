"""``spread``: an open loop of round(rate_per_s * seconds) requests whose
gaps are the exponential distribution's quantiles at evenly spaced
probabilities, in an order drawn from the traffic file's ``order_seed``,
scaled so that they sum to the window.  The mean rate of a Poisson
process without its draw-to-draw spread: every run gets the same arrivals.

    "arrivals": {"process": "spread", "rate_per_s": 0.28, "order_seed": 1}
"""
from typing import Dict, List

import numpy as np


def dues(arrivals: Dict, seconds: float,
         rng: np.random.Generator) -> List[float]:
    """Due times in seconds after the window opens; the first is 0."""
    n = max(1, int(round(arrivals["rate_per_s"] * seconds)))
    u = (np.arange(n) + 0.5) / n
    gaps = rng.permutation(-np.log1p(-u))
    gaps *= seconds / gaps.sum()
    return [float(t) for t in np.concatenate([[0.0], np.cumsum(gaps)[:-1]])]

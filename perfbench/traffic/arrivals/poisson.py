"""poisson: a Poisson process of the mix's `rate_per_s`, with the same work
for every seed: the gaps between arrivals are a fixed set of exponential
quantiles, scaled to fill the window; the seed only orders them."""
from harness.traffic import exponential_times


def due(rng, n_req: int, seconds: float, mix: dict):
    """Arrival times of n_req requests from the window's start, ascending."""
    return exponential_times(rng, n_req, seconds)

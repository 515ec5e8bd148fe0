"""Open loop, Poisson arrivals at ``rate_rps``: exponential gaps."""
import numpy as np

CLOSED = False


def gaps(spec, u):
    """Inter-arrival gaps (seconds) at quantiles ``u`` of the law."""
    return -np.log1p(-np.asarray(u)) / float(spec["rate_rps"])

"""Open loop, bursty arrivals: Gamma gaps of mean ``1 / rate_rps`` and
coefficient of variation ``cv`` (``cv`` 1 is Poisson, above 1 bursts)."""
import numpy as np
from scipy import stats

CLOSED = False


def gaps(spec, u):
    """Inter-arrival gaps (seconds) at quantiles ``u`` of the law."""
    shape = 1.0 / float(spec["cv"]) ** 2
    scale = 1.0 / (float(spec["rate_rps"]) * shape)
    return stats.gamma.ppf(np.asarray(u), shape, scale=scale)

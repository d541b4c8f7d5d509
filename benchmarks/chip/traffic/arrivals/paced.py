"""A fixed cadence: ``round(rate_rps * seconds)`` requests, evenly spaced,
each due half a gap after the last."""

import numpy as np


def times(p, seconds, rng):
    n = int(round(p["rate_rps"] * seconds))
    return (np.arange(n) + 0.5) * (seconds / n)

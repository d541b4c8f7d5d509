"""Poisson arrivals at ``rate_rps``: exponential gaps from the fixed
stream, every arrival before the window's end."""

import numpy as np


def times(p, seconds, rng):
    rate = float(p["rate_rps"])
    gaps = rng.exponential(1.0 / rate, int(rate * seconds * 2 + 20))
    t = np.cumsum(gaps)
    return t[t < seconds]

"""Bursts: Poisson arrivals during ``on_s`` seconds, none during the next
``off_s``, repeated, at a mean of ``rate_rps`` over the cycle."""

import numpy as np


def times(p, seconds, rng):
    on, off = float(p["on_s"]), float(p["off_s"])
    peak = float(p["rate_rps"]) * (on + off) / on
    gaps = rng.exponential(1.0 / peak, int(peak * seconds * 2 + 20))
    busy = np.cumsum(gaps)                    # seconds of "on" time
    t = busy + np.floor(busy / on) * off      # skip each off period
    return t[t < seconds]

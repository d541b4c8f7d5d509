"""The kingsnake stand-in: a gyroid lattice on [0, 1]^3, iso value 0 (the
program's ``repro.data.volumes.kingsnake``)."""

import numpy as np


def field(x, y, z):
    k = np.float32(6 * np.pi)
    return (np.sin(k * x) * np.cos(k * y)
            + np.sin(k * y) * np.cos(k * z)
            + np.sin(k * z) * np.cos(k * x))

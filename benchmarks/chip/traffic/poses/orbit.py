"""Viewers orbiting the scene: ``sessions`` viewers, a ``far_share`` of them
at ``far_radius`` times the rig radius (the coarse LOD rung), the rest on
it.  Each dwells on a pose for a Zipf(``dwell_a``) number of its requests
(at most ``dwell_max``), then steps its azimuth by ``step_deg``.

The fixed stream picks which viewer sends each request (an equal share
each), which viewers are far and every dwell; the seed picks each viewer's
start azimuth, elevation (within ``elevation_deg``) and direction."""

import numpy as np

import scene


def make(p, n, fixed, rng, center, rig_radius):
    S = int(p["sessions"])
    far = np.zeros(S, bool)
    far[fixed.permutation(S)[:int(round(p["far_share"] * S))]] = True
    share = np.full(S, n // S)
    share[fixed.permutation(S)[:n % S]] += 1
    order = fixed.permutation(np.repeat(np.arange(S), share))
    dwell_left = np.zeros(S, int)
    pose_no = np.full(S, -1)
    steps = []
    for s in order:
        if dwell_left[s] == 0:
            pose_no[s] += 1
            dwell_left[s] = min(int(fixed.zipf(p["dwell_a"])),
                                int(p["dwell_max"]))
        dwell_left[s] -= 1
        steps.append(pose_no[s])
    el_lo, el_hi = np.radians(p["elevation_deg"])
    azim = rng.uniform(0, 2 * np.pi, S)
    elev = rng.uniform(el_lo, el_hi, S)
    sign = rng.choice([-1.0, 1.0], S)
    step = np.radians(p["step_deg"])
    radius = np.where(far, rig_radius * p["far_radius"], rig_radius)
    views = [(scene.pose(center, radius[s], azim[s] + sign[s] * step * k,
                         elev[s]), bool(far[s]))
             for s, k in zip(order, steps)]
    return views, []

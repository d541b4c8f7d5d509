"""Viewers flipping between ``bookmarks`` saved poses, a ``far_share`` of
them at ``far_radius`` times the rig radius.  Each request picks a bookmark
by Zipf(``zipf_s``) rank; with ``prime_cache`` set-up requests every
bookmark once.

The fixed stream picks which bookmarks are far and the rank each request
picks; the seed picks where each bookmark looks from."""

import numpy as np

import scene


def make(p, n, fixed, rng, center, rig_radius):
    B = int(p["bookmarks"])
    far = np.zeros(B, bool)
    far[fixed.permutation(B)[:int(round(p["far_share"] * B))]] = True
    w = 1.0 / np.arange(1, B + 1) ** p["zipf_s"]
    ranks = fixed.choice(B, n, p=w / w.sum())
    el_lo, el_hi = np.radians(p["elevation_deg"])
    azim = rng.uniform(0, 2 * np.pi, B)
    elev = rng.uniform(el_lo, el_hi, B)
    radius = np.where(far, rig_radius * p["far_radius"], rig_radius)
    marks = [(scene.pose(center, radius[b], azim[b], elev[b]), bool(far[b]))
             for b in range(B)]
    views = [marks[r] for r in ranks]
    return views, (marks if p.get("prime_cache") else [])

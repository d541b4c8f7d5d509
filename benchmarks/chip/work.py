"""The yardstick: work the 3D-GS math requires, whatever implements it.

Counts come from the benchmark's own projection (``reference.py``): for each
view and tile, n_t is the number of splats whose radius circle meets the
tile, capped at the configuration's K.  A pixel-splat pair is one splat of a
tile's list at one pixel of that tile, so a view has
sum_t min(n_t, K) * tile_h * tile_w pairs.  Nothing here reads the program's
tier caps, padded K or assignment algorithm, so a faster or leaner
implementation of the same step shows as a higher share.

Operation counts (one add, multiply, compare, select, max, min, divide,
square root or exponential each counts 1), derived from the compositing
equations of ``kernels/rasterize.py`` and ``reference.composite``:

Forward, per pixel-splat pair (RASTER_FWD_FLOPS = 27):
  dx = px - mx, dy = py - my ............................ 2
  sigma = 0.5 (A dx^2 + C dy^2) + B dx dy ............... 9
  g = exp(-max(sigma, 0)) ............................... 3
  alpha = min(o g, 0.99); alpha < 1/255 -> 0 ............ 4
  w = T alpha; T <- T (1 - alpha) ....................... 3
  r, g, b += w c ........................................ 6

Backward, per pair (RASTER_BWD_FLOPS = 79), in one front-to-back sweep:
  the forward terms above, colour prefix sums in place of the output ... 27
  dL/dalpha: 3 x (T c - (C - prefix) / (1 - alpha)) and its weighting
    by the output gradient, plus the coverage term ................... 20
  masks (alpha live, a g < 0.99) and dL/dsigma = -a g dL/dalpha ........ 5
  the nine feature gradients summed over the tile's pixels:
    mean x, y: 2 x 5; conic A, B, C: 4 + 3 + 4; r, g, b: 3 x 2;
    opacity: 2 ....................................................... 27
  Derived from the kernel's body line by line; it recomputes the forward
  terms, which the count keeps, since a single-sweep backward needs them.

Bytes that must cross HBM per tile: the forward reads 9 float32 features
per listed splat and writes 4 float32 planes; the backward reads the
features, the forward's 4 planes and their 4 gradient planes, and writes
9 feature gradients per listed splat.

Whole step (``mfu.train``), counted from the reference's equations:
  projection and kernel features, per splat and view (PROJECT_FLOPS = 266):
    world to camera 18, perspective 7, Jacobian 8, quaternion normalise 12,
    rotation matrix 36, R S 9 + exp 3, covariance 45, J R 30, T Sigma T^T
    50, dilation and eigenvalue radius 15, four sigmoids 16, culling 10,
    conic 7;
  loss, per pixel and colour channel (LOSS_FLOPS = 171): L1 4; five
    separable 7-tap Gaussian blurs 5 x 28; products and moments 9; SSIM
    ratio 12; masking and sums 6;
  the backward of projection and loss counted as twice their forward;
  Adam, per trainable parameter (ADAM_FLOPS = 14), 14 parameters a splat.
Tile assignment is left out: its cost is the implementation's choice.
"""

from __future__ import annotations

RASTER_FWD_FLOPS = 27
RASTER_BWD_FLOPS = 79
PROJECT_FLOPS = 266
LOSS_FLOPS = 171
ADAM_FLOPS = 14
F32 = 4
FEATURES = 9
PLANES = 4


def raster_fwd(pairs: float, splat_refs: float, pixels: float):
    """-> (flops, bytes) of the forward compositing of a tile set with
    ``pairs`` pixel-splat pairs, ``splat_refs`` listed splats (sum of
    min(n_t, K)) and ``pixels`` tile pixels."""
    return (RASTER_FWD_FLOPS * pairs,
            F32 * (FEATURES * splat_refs + PLANES * pixels))


def raster_bwd(pairs: float, splat_refs: float, pixels: float):
    return (RASTER_BWD_FLOPS * pairs,
            F32 * (2 * FEATURES * splat_refs + 2 * PLANES * pixels))


def roofline_share(flops: float, nbytes: float, seconds: float,
                   peak: dict):
    """-> (percent of the roofline reached, "flops" or "bytes": the bound).
    None when there was no time to share (nothing ran)."""
    if not seconds or seconds <= 0 or not (flops or nbytes):
        return None, None
    t_f = flops / peak["flops_per_s"]
    t_b = nbytes / peak["hbm_bytes_per_s"]
    return 100.0 * max(t_f, t_b) / seconds, "flops" if t_f >= t_b else "bytes"


class CappedCounts:
    """sum over tiles of min(n_t, K) for one view of one splat set; its
    compiled programs live as long as the object."""

    def __init__(self, grid, K: int):
        self.grid, self.K = grid, K
        self._fns = {}

    def __call__(self, tr, active, view, f) -> float:
        import jax
        import jax.numpy as jnp

        import reference as ref
        grid, K, fns = self.grid, self.K, self._fns
        if "need" not in fns:
            fns["need"] = jax.jit(lambda t, a, v, fo: ref.needed_slots(
                ref.project(t, a, v, fo, grid), grid))
        slots = ref.slots_for(int(fns["need"](tr, active, view, f)))
        if slots not in fns:
            fns[slots] = jax.jit(lambda t, a, v, fo: jnp.minimum(
                ref.tile_counts(ref.project(t, a, v, fo, grid), grid, slots),
                K).sum())
        return float(fns[slots](tr, active, view, f))


def train_window(*, steps, pairs, splat_refs, tiles, pixels, splat_views,
                 params) -> dict:
    """Work of a training window: ``pairs``/``splat_refs`` summed over the
    window's steps, ``tiles``/``pixels`` the tiles and tile pixels
    rendered, ``splat_views`` live splats times views, ``params`` the
    trainable parameters."""
    fwd = raster_fwd(pairs, splat_refs, pixels)
    bwd = raster_bwd(pairs, splat_refs, pixels)
    useful = (3 * PROJECT_FLOPS * splat_views
              + (RASTER_FWD_FLOPS + RASTER_BWD_FLOPS) * pairs
              + 3 * LOSS_FLOPS * 3 * pixels
              + ADAM_FLOPS * params * steps)
    return {"raster_fwd": fwd, "raster_bwd": bwd, "step_flops": useful,
            "steps": steps, "pairs": pairs, "tiles": tiles}

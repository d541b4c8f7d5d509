"""Pallas TPU tile rasterizer for 3D-GS compositing (forward + backward).

TPU-native redesign of the CUDA 3D-GS rasterizer (DESIGN.md §3):

* one Pallas program per image tile (grid = (T,));
* the tile's fixed-K splat list (K, FEAT_DIM) lives in VMEM — one 4 KB block
  for K=64 — loaded to registers once per program;
* the (tile_h, tile_w) pixel accumulators (transmittance + 3 color channels)
  are VREG-resident f32 planes; with the production tile shape (8, 128) each
  compositing step is one VREG row op per plane;
* front-to-back compositing is a ``fori_loop`` over K — branchless: the GPU
  per-pixel early-termination break becomes masked lanes (alpha below 1/255
  contributes exactly 0), the alpha clamp (0.99) and sigma>=0 guard match the
  3D-GS reference semantics;
* the backward pass is a *single forward* loop (no reverse sweep): with
  C = sum_k w_k rgb_k, w_k = T_k alpha_k, the suffix sums the gradient needs
  are recovered as  S_k = C - prefix_k, so d out / d alpha_k =
  T_k rgb_k - S_k / (1 - alpha_k) using only the running prefix — this is the
  TPU replacement for the CUDA back-to-front replay.

VMEM budget per program (production tile 8x128, K=64):
  feats 4 KB + out 16 KB + gout/out residuals 32 KB (bwd) + accumulators in
  VREGs — far below the ~16 MB/core VMEM limit, so many programs pipeline.

Layouts: feats (T, K, 16) f32, origins (T, 2) f32, out (T, 4, th, tw) f32
(channels [r, g, b, coverage]).

The two ``pallas_call``s are named ``raster_fwd`` and ``raster_bwd``, so the
compiled kernels keep one name whatever transform calls them.

K is a trace-time constant, not a baked-in config: each pallas_call
specializes its (1, K, F) block spec and fori_loop bound to the incoming
feats shape.  The variable-K tiered dispatch (kernels/ops.
rasterize_tiles_tiered) relies on exactly this — it calls these kernels
once per occupancy tier with that tier's own (cap_i, K_i, F) table, so a
K=16 tier runs a 16-step compositing loop over a 1 KB VMEM block instead
of paying the top tier's K everywhere.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

ALPHA_MAX = 0.99
ALPHA_MIN = 1.0 / 255.0


def _pixel_grids(origin_x, origin_y, th: int, tw: int):
    # integer iota cast to f32: the TPU lowering only builds integer iotas
    col = lax.broadcasted_iota(jnp.int32, (th, tw), 1).astype(jnp.float32)
    row = lax.broadcasted_iota(jnp.int32, (th, tw), 0).astype(jnp.float32)
    return origin_x + 0.5 + col, origin_y + 0.5 + row


def _alpha_terms(f, px, py):
    """Shared fwd/bwd per-splat math. f: indexable feature row (f[j] is a
    scalar — read straight from the SMEM feature block inside the kernels)."""
    dx = px - f[0]
    dy = py - f[1]
    sigma = 0.5 * (f[2] * dx * dx + f[4] * dy * dy) + f[3] * dx * dy
    g = jnp.exp(-jnp.maximum(sigma, 0.0))
    a_g = f[8] * g
    alpha = jnp.minimum(a_g, ALPHA_MAX)
    live = alpha >= ALPHA_MIN
    alpha = jnp.where(live, alpha, 0.0)
    return dx, dy, sigma, g, a_g, alpha, live


class _Row:
    """Splat k's feature row as lazy scalar reads from the (1, K, F) SMEM
    block: ``_Row(ref, k)[j]`` is one scalar load, so the loop never
    dynamic-slices a loaded vector (which has no TPU lowering)."""

    def __init__(self, ref, k):
        self.ref, self.k = ref, k

    def __getitem__(self, j):
        return self.ref[0, self.k, j]


def _in_specs(K: int, F: int, n_planes: int, th: int, tw: int):
    """Block specs shared by fwd/bwd: the per-tile feature list and origin
    live in SMEM (scalar reads, broadcast into the VREG planes); the
    (4, th, tw) image planes stream through VMEM."""
    specs = [
        pl.BlockSpec((1, K, F), lambda t: (t, 0, 0),
                     memory_space=pltpu.SMEM),
        pl.BlockSpec((1, 1, 2), lambda t: (t, 0, 0),
                     memory_space=pltpu.SMEM),
    ]
    return specs + [pl.BlockSpec((1, 4, th, tw), lambda t: (t, 0, 0, 0))
                    for _ in range(n_planes)]


def _origin_blocks(origins):
    """(T, 2) -> (T, 1, 2): a (1, 1, 2) block spans the array's last two
    dims, which the TPU lowering requires of a block that is not
    (8, 128)-aligned."""
    return origins.astype(jnp.float32).reshape(-1, 1, 2)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(feat_ref, origin_ref, out_ref, *, K: int, th: int, tw: int):
    px, py = _pixel_grids(origin_ref[0, 0, 0], origin_ref[0, 0, 1], th, tw)

    def body(k, carry):
        trans, r, g, b = carry
        f = _Row(feat_ref, k)
        *_, alpha, _ = _alpha_terms(f, px, py)
        w = trans * alpha
        return (trans * (1.0 - alpha),
                r + w * f[5], g + w * f[6], b + w * f[7])

    zero = jnp.zeros((th, tw), jnp.float32)
    trans, r, g, b = lax.fori_loop(
        0, K, body, (jnp.ones((th, tw), jnp.float32), zero, zero, zero)
    )
    out_ref[0, 0] = r
    out_ref[0, 1] = g
    out_ref[0, 2] = b
    out_ref[0, 3] = 1.0 - trans


def rasterize_fwd(feats, origins, *, tile_h: int, tile_w: int,
                  interpret: bool = False):
    T, K, F = feats.shape
    kernel = functools.partial(_fwd_kernel, K=K, th=tile_h, tw=tile_w)
    return pl.pallas_call(
        kernel,
        grid=(T,),
        in_specs=_in_specs(K, F, 0, tile_h, tile_w),
        out_specs=pl.BlockSpec((1, 4, tile_h, tile_w), lambda t: (t, 0, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((T, 4, tile_h, tile_w), jnp.float32),
        interpret=interpret,
        name="raster_fwd",
    )(feats.astype(jnp.float32), _origin_blocks(origins))


# ---------------------------------------------------------------------------
# Backward (single forward sweep, prefix-sum trick)
# ---------------------------------------------------------------------------


def _bwd_kernel(feat_ref, origin_ref, out_ref, gout_ref, gfeat_ref,
                *, K: int, F: int, th: int, tw: int):
    px, py = _pixel_grids(origin_ref[0, 0, 0], origin_ref[0, 0, 1], th, tw)
    c_r, c_g, c_b = out_ref[0, 0], out_ref[0, 1], out_ref[0, 2]
    t_final = 1.0 - out_ref[0, 3]
    g_r, g_g, g_b, g_cov = (gout_ref[0, 0], gout_ref[0, 1],
                            gout_ref[0, 2], gout_ref[0, 3])

    def body(k, carry):
        trans, pr, pg, pb = carry
        f = _Row(feat_ref, k)
        dx, dy, sigma, g, a_g, alpha, live = _alpha_terms(f, px, py)
        w = trans * alpha
        pr = pr + w * f[5]
        pg = pg + w * f[6]
        pb = pb + w * f[7]
        denom = 1.0 - alpha                   # >= 1 - ALPHA_MAX = 0.01
        g_alpha = (
            g_r * (trans * f[5] - (c_r - pr) / denom)
            + g_g * (trans * f[6] - (c_g - pg) / denom)
            + g_b * (trans * f[7] - (c_b - pb) / denom)
            + g_cov * (t_final / denom)
        )
        mask = live & (a_g < ALPHA_MAX)
        g_ag = jnp.where(mask, g_alpha, 0.0)
        g_sigma = jnp.where(sigma > 0.0, -a_g * g_ag, 0.0)
        row = (
            jnp.sum(-(f[2] * dx + f[3] * dy) * g_sigma),     # d/d mean_x
            jnp.sum(-(f[4] * dy + f[3] * dx) * g_sigma),     # d/d mean_y
            jnp.sum(0.5 * dx * dx * g_sigma),                # d/d conic A
            jnp.sum(dx * dy * g_sigma),                      # d/d conic B
            jnp.sum(0.5 * dy * dy * g_sigma),                # d/d conic C
            jnp.sum(g_r * w),                                # d/d r
            jnp.sum(g_g * w),                                # d/d g
            jnp.sum(g_b * w),                                # d/d b
            jnp.sum(g_ag * g),                               # d/d alpha
        )
        # one scalar store per feature column into the SMEM gradient block;
        # the unused tail columns are written as exact zeros
        for j in range(F):
            gfeat_ref[0, k, j] = row[j] if j < len(row) else jnp.float32(0)
        return (trans * denom, pr, pg, pb)

    zero = jnp.zeros((th, tw), jnp.float32)
    lax.fori_loop(0, K, body,
                  (jnp.ones((th, tw), jnp.float32), zero, zero, zero))


def rasterize_bwd(feats, origins, out, gout, *, tile_h: int, tile_w: int,
                  interpret: bool = False):
    T, K, F = feats.shape
    kernel = functools.partial(_bwd_kernel, K=K, F=F, th=tile_h, tw=tile_w)
    return pl.pallas_call(
        kernel,
        grid=(T,),
        in_specs=_in_specs(K, F, 2, tile_h, tile_w),
        out_specs=pl.BlockSpec((1, K, F), lambda t: (t, 0, 0),
                               memory_space=pltpu.SMEM),
        out_shape=jax.ShapeDtypeStruct((T, K, F), jnp.float32),
        interpret=interpret,
        name="raster_bwd",
    )(
        feats.astype(jnp.float32),
        _origin_blocks(origins),
        out.astype(jnp.float32),
        gout.astype(jnp.float32),
    )

"""Plain reference of the 3D-GS step and render the cells time.

Straightforward ``jax.numpy`` in float32, with no kernel, cache, tier or
batching, written from the published equations (Kerbl et al. 2023, EWA
splatting) and the configuration's stated semantics.  It imports nothing of
the program under test and takes nothing the program made: it is handed the
benchmark's own scene data and cameras.

Semantics it states:

- projection: pinhole camera looking down +z, EWA 2-D covariance with a
  0.3 px dilation, radius ``ceil(3 sqrt(lambda_max))``, culled when behind
  ``near``, off-screen, inactive, below alpha 1/255 or degenerate;
- tile assignment: a splat overlaps a tile when its radius circle meets the
  tile's closed rectangle; each tile keeps its K nearest overlapping splats,
  ties broken by the lower splat row;
- compositing: front to back over the tile's list, alpha clamped at 0.99 and
  dropped below 1/255, no early termination; output r, g, b and coverage;
- loss: per view, masked L1 and masked D-SSIM (7x7 Gaussian window, sigma
  1.5, zero padding inside each tile), pooled over the partitions' tiles,
  ``(1 - lambda) L1 + lambda (1 - SSIM) / 2``, averaged over the views;
- Adam with bias correction and per-group learning rates.

Assignment is by sort: each splat enumerates the tiles of its bounding box
(at most ``slots`` of them, a static bound measured from the data by
``needed_slots``), keeps the ones its circle meets, and one sort by
(tile, depth, row) lays every tile's list out in order.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

NEAR = 0.05
ALPHA_MIN = 1.0 / 255.0
ALPHA_MAX = 0.99
DILATE = 0.3
#: widening of each splat's tile bounding box (px), so the enumerated tiles
#: are a superset of the exact circle test, which then decides
BBOX_EPS = 1e-2
TRAINABLE = ("means", "log_scales", "quats", "opacity_logit", "colors")


class Grid(NamedTuple):
    width: int
    height: int
    tile_h: int
    tile_w: int

    @property
    def nx(self):
        return -(-self.width // self.tile_w)

    @property
    def ny(self):
        return -(-self.height // self.tile_h)

    @property
    def n_tiles(self):
        return self.nx * self.ny


# ---------------------------------------------------------------------------
# Projection
# ---------------------------------------------------------------------------


def _mm(a, b):
    """(..., m, k) @ (..., k, n) as an elementwise multiply-and-sum: exact
    float32 on any backend."""
    return (a[..., :, :, None] * b[..., None, :, :]).sum(-2)


def rotation(q):
    q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
    w, x, y, z = q[..., 0], q[..., 1], q[..., 2], q[..., 3]
    return jnp.stack([
        jnp.stack([1 - 2 * (y * y + z * z), 2 * (x * y - w * z),
                   2 * (x * z + w * y)], -1),
        jnp.stack([2 * (x * y + w * z), 1 - 2 * (x * x + z * z),
                   2 * (y * z - w * x)], -1),
        jnp.stack([2 * (x * z - w * y), 2 * (y * z + w * x),
                   1 - 2 * (x * x + y * y)], -1)], axis=-2)


class Splats(NamedTuple):
    mean: jax.Array      # (N, 2) pixels
    cov: jax.Array       # (N, 3) a, b, c of [[a, b], [b, c]]
    depth: jax.Array     # (N,)
    rgb: jax.Array       # (N, 3)
    alpha: jax.Array     # (N,)
    radius: jax.Array    # (N,)
    valid: jax.Array     # (N,) bool


def project(tr: dict, active, view, f, grid: Grid) -> Splats:
    """One camera: ``view`` (4, 4) world -> camera, focal ``f`` in px."""
    R = view[:3, :3]
    t = view[:3, 3]
    p = (tr["means"][:, None, :] * R).sum(-1) + t
    x, y, z = p[:, 0], p[:, 1], p[:, 2]
    zc = jnp.maximum(z, NEAR)
    u = f * x / zc + grid.width / 2.0
    v = f * y / zc + grid.height / 2.0
    zero = jnp.zeros_like(zc)
    J = jnp.stack([jnp.stack([f / zc, zero, -f * x / (zc * zc)], -1),
                   jnp.stack([zero, f / zc, -f * y / (zc * zc)], -1)], -2)
    RS = rotation(tr["quats"]) * jnp.exp(tr["log_scales"])[:, None, :]
    cov3 = _mm(RS, jnp.swapaxes(RS, -1, -2))
    T = _mm(J, R)
    cov2 = _mm(_mm(T, cov3), jnp.swapaxes(T, -1, -2))
    a = cov2[:, 0, 0] + DILATE
    b = cov2[:, 0, 1]
    c = cov2[:, 1, 1] + DILATE
    det = a * c - b * b
    mid = 0.5 * (a + c)
    lam = mid + jnp.sqrt(jnp.maximum(mid * mid - det, 1e-9))
    radius = jnp.ceil(3.0 * jnp.sqrt(jnp.maximum(lam, 1e-9)))
    alpha = jax.nn.sigmoid(tr["opacity_logit"])
    valid = ((z > NEAR)
             & (u + radius > 0) & (u - radius < grid.width)
             & (v + radius > 0) & (v - radius < grid.height)
             & active & (alpha > ALPHA_MIN) & (det > 1e-12))
    return Splats(mean=jnp.stack([u, v], -1), cov=jnp.stack([a, b, c], -1),
                  depth=z, rgb=jax.nn.sigmoid(tr["colors"]), alpha=alpha,
                  radius=radius, valid=valid)


# ---------------------------------------------------------------------------
# Tile assignment
# ---------------------------------------------------------------------------


def _bbox(sp: Splats, grid: Grid):
    """Inclusive tile-index bounding box of each splat's circle."""
    mx, my, r = sp.mean[:, 0], sp.mean[:, 1], sp.radius
    fl = lambda v, n, s: jnp.clip(jnp.floor(v / s), 0, n - 1).astype(jnp.int32)
    x0 = fl(mx - r - BBOX_EPS, grid.nx, grid.tile_w)
    x1 = fl(mx + r + BBOX_EPS, grid.nx, grid.tile_w)
    y0 = fl(my - r - BBOX_EPS, grid.ny, grid.tile_h)
    y1 = fl(my + r + BBOX_EPS, grid.ny, grid.tile_h)
    return x0, x1, y0, y1


def needed_slots(sp: Splats, grid: Grid):
    """() int32: the most bounding-box tiles any valid splat has."""
    x0, x1, y0, y1 = _bbox(sp, grid)
    n = (x1 - x0 + 1) * (y1 - y0 + 1)
    return jnp.where(sp.valid, n, 0).max()


def slots_for(need: int) -> int:
    """Static slot count for a measured need: the next power of two, so
    scenes of nearby splat sizes share one compiled program."""
    s = 4
    while s < need:
        s *= 2
    return s


def candidates(sp: Splats, grid: Grid, slots: int):
    """-> (tile (N, slots) int32 with ``n_tiles`` where no overlap, hit)."""
    x0, x1, y0, y1 = _bbox(sp, grid)
    nbx = x1 - x0 + 1
    nby = y1 - y0 + 1
    b = jnp.arange(slots, dtype=jnp.int32)[None, :]
    tx = x0[:, None] + b % nbx[:, None]
    ty = y0[:, None] + b // nbx[:, None]
    lox = (tx * grid.tile_w).astype(jnp.float32)
    loy = (ty * grid.tile_h).astype(jnp.float32)
    mx, my = sp.mean[:, 0:1], sp.mean[:, 1:2]
    dx = mx - jnp.clip(mx, lox, lox + grid.tile_w)
    dy = my - jnp.clip(my, loy, loy + grid.tile_h)
    r = sp.radius[:, None]
    hit = ((dx * dx + dy * dy) <= r * r) & sp.valid[:, None] \
        & (b < (nbx * nby)[:, None])
    tile = jnp.where(hit, ty * grid.nx + tx, grid.n_tiles)
    return tile, hit


def tile_counts(sp: Splats, grid: Grid, slots: int):
    """(T,) int32: splats whose circle meets each tile (uncapped)."""
    tile, hit = candidates(sp, grid, slots)
    return jnp.zeros((grid.n_tiles + 1,), jnp.int32) \
        .at[tile.reshape(-1)].add(hit.reshape(-1).astype(jnp.int32))[:-1]


def assign(sp: Splats, grid: Grid, K: int, slots: int):
    """-> idx (T, K) int32 splat rows, live (T, K) bool: each tile's K
    nearest overlapping splats in front-to-back order."""
    N = sp.depth.shape[0]
    tile, hit = candidates(sp, grid, slots)
    rows = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32)[:, None],
                            tile.shape)
    depth = jnp.broadcast_to(sp.depth[:, None], tile.shape)
    # stable on (tile, depth): equal keys keep their row order
    s_tile, _, s_row = lax.sort(
        (tile.reshape(-1), depth.reshape(-1), rows.reshape(-1)), num_keys=2,
        is_stable=True)
    counts = jnp.zeros((grid.n_tiles + 1,), jnp.int32) \
        .at[tile.reshape(-1)].add(hit.reshape(-1).astype(jnp.int32))[:-1]
    starts = jnp.cumsum(counts) - counts
    k = jnp.arange(K, dtype=jnp.int32)[None, :]
    pos = jnp.minimum(starts[:, None] + k, N * slots - 1)
    live = k < counts[:, None]
    return jnp.where(live, s_row[pos], 0), live


# ---------------------------------------------------------------------------
# Compositing
# ---------------------------------------------------------------------------


def features(sp: Splats):
    """(N, 9): mean x, y, conic A, B, C, r, g, b, opacity (0 if culled)."""
    a, b, c = sp.cov[:, 0], sp.cov[:, 1], sp.cov[:, 2]
    det = jnp.maximum(a * c - b * b, 1e-12)
    return jnp.concatenate([
        sp.mean, jnp.stack([c / det, -b / det, a / det], -1), sp.rgb,
        jnp.where(sp.valid, sp.alpha, 0.0)[:, None]], -1)


def tile_origins(grid: Grid):
    t = jnp.arange(grid.n_tiles)
    return jnp.stack([(t % grid.nx) * grid.tile_w,
                      (t // grid.nx) * grid.tile_h], -1).astype(jnp.float32)


def composite(feat, idx, live, grid: Grid):
    """(T, 4, th, tw): r, g, b premultiplied, and coverage."""
    f = feat[idx]                                          # (T, K, 9)
    op = jnp.where(live, f[..., 8], 0.0)
    o = tile_origins(grid)
    px = o[:, 0, None, None] + 0.5 + jnp.arange(grid.tile_w,
                                                dtype=jnp.float32)
    py = o[:, 1, None, None] + 0.5 + jnp.arange(grid.tile_h,
                                                dtype=jnp.float32)[:, None]
    dx = px[:, None] - f[..., 0, None, None]               # (T, K, th, tw)
    dy = py[:, None] - f[..., 1, None, None]
    sigma = 0.5 * (f[..., 2, None, None] * dx * dx
                   + f[..., 4, None, None] * dy * dy) \
        + f[..., 3, None, None] * dx * dy
    alpha = jnp.minimum(op[..., None, None]
                        * jnp.exp(-jnp.maximum(sigma, 0.0)), ALPHA_MAX)
    alpha = jnp.where(alpha < ALPHA_MIN, 0.0, alpha)
    keep = 1.0 - alpha
    trans = jnp.concatenate([jnp.ones_like(keep[:, :1]),
                             jnp.cumprod(keep, axis=1)[:, :-1]], axis=1)
    w = trans * alpha
    rgb = (w[:, :, None] * f[..., 5:8, None, None]).sum(1)
    cov = 1.0 - jnp.prod(keep, axis=1)
    return jnp.concatenate([rgb, cov[:, None]], axis=1)


def render_tiles(tr, active, view, f, grid: Grid, K: int, slots: int):
    """Differentiable in ``tr`` (the assignment is not)."""
    sp = project(tr, active, view, f, grid)
    held = jax.tree.map(lax.stop_gradient, sp)
    idx, live = assign(held, grid, K, slots)
    return composite(features(sp), idx, live, grid)


def untile(tiles, grid: Grid):
    """(T, C, th, tw) -> (H, W, C)."""
    C = tiles.shape[1]
    img = tiles.reshape(grid.ny, grid.nx, C, grid.tile_h, grid.tile_w)
    img = img.transpose(0, 3, 1, 4, 2).reshape(grid.ny * grid.tile_h,
                                               grid.nx * grid.tile_w, C)
    return img[:grid.height, :grid.width]


def to_tiles(img, grid: Grid):
    """(H, W, C) -> (T, C, th, tw), zero beyond the image."""
    C = img.shape[-1]
    Hp, Wp = grid.ny * grid.tile_h, grid.nx * grid.tile_w
    img = jnp.pad(img, ((0, Hp - img.shape[0]), (0, Wp - img.shape[1]),
                        (0, 0)))
    t = img.reshape(grid.ny, grid.tile_h, grid.nx, grid.tile_w, C)
    return t.transpose(0, 2, 4, 1, 3).reshape(grid.n_tiles, C, grid.tile_h,
                                              grid.tile_w)


def dilate(mask, iters: int = 2):
    """Binary dilation by a 3x3 square, ``iters`` times, (H, W) bool."""
    m = mask
    for _ in range(iters):
        p = jnp.pad(m, 1)
        H, W = m.shape
        m = jnp.zeros_like(m)
        for dy in range(3):
            for dx in range(3):
                m = m | p[dy:dy + H, dx:dx + W]
    return m


# ---------------------------------------------------------------------------
# Loss and Adam
# ---------------------------------------------------------------------------


def _gauss1d(size: int = 7, sigma: float = 1.5):
    x = np.arange(size, dtype=np.float64) - (size - 1) / 2.0
    g = np.exp(-(x ** 2) / (2 * sigma ** 2))
    return (g / g.sum()).astype(np.float32)


def _blur(x, g):
    """Zero-padded 'same' separable Gaussian over the last two axes."""
    r = len(g) // 2
    for ax in (-1, -2):
        n = x.shape[ax]
        pad = [(0, 0)] * x.ndim
        pad[ax] = (r, r)
        p = jnp.pad(x, pad)
        x = sum(float(g[k]) * lax.slice_in_dim(p, k, k + n, axis=ax % x.ndim)
                for k in range(len(g)))
    return x


def loss_partials(pred, gt, mask, win: int = 7):
    """Masked L1 and D-SSIM sums over tiles: pred/gt (M, 3, th, tw), mask
    (M, th, tw) -> (l1_num, l1_den, ssim_num, ssim_den)."""
    g = _gauss1d(win)
    m = mask.astype(jnp.float32)[:, None]
    l1n = (jnp.abs(pred - gt) * m).sum()
    den = m.sum() * pred.shape[1]
    c1, c2 = 0.01 ** 2, 0.03 ** 2
    mu_a, mu_b = _blur(pred, g), _blur(gt, g)
    s_aa = _blur(pred * pred, g) - mu_a * mu_a
    s_bb = _blur(gt * gt, g) - mu_b * mu_b
    s_ab = _blur(pred * gt, g) - mu_a * mu_b
    ssim = ((2 * mu_a * mu_b + c1) * (2 * s_ab + c2)) / (
        (mu_a * mu_a + mu_b * mu_b + c1) * (s_aa + s_bb + c2))
    return l1n, den, (ssim * m).sum(), den


def view_loss(parts, lam: float):
    """Pool the partitions' partial sums of one view -> its loss."""
    l1n, l1d, sn, sd = (sum(p[i] for p in parts) for i in range(4))
    return ((1 - lam) * l1n / jnp.maximum(l1d, 1.0)
            + lam * (1.0 - sn / jnp.maximum(sd, 1.0)) / 2.0)


def make_loss(grid: Grid, K: int, slots: int, lam: float):
    """loss(trs, actives, views, f, gt_tiles, mask_tiles): ``trs`` is a
    list of per-partition trainable dicts, ``views`` (V, 4, 4), gt_tiles
    (P, V, T, 3, th, tw), mask_tiles (P, V, T, th, tw)."""
    @jax.checkpoint
    def shade(feat, idx, live, gt, mask):
        return loss_partials(composite(feat, idx, live, grid)[:, :3], gt,
                             mask)

    def part_view(tr, active, view, f, gt, mask):
        sp = project(tr, active, view, f, grid)
        idx, live = assign(jax.tree.map(lax.stop_gradient, sp), grid, K,
                           slots)
        return shade(features(sp), idx, live, gt, mask)

    def loss(trs, actives, views, f, gt_tiles, mask_tiles):
        per_view = []
        for v in range(views.shape[0]):
            parts = [part_view(trs[p], actives[p], views[v], f,
                               gt_tiles[p, v], mask_tiles[p, v])
                     for p in range(len(trs))]
            per_view.append(view_loss(parts, lam))
        return sum(per_view) / len(per_view)
    return loss


def adam(tr, m, v, grads, step: int, lrs: dict, b1: float, b2: float,
         eps: float):
    """One Adam update (``step`` counts from 1) -> (tr, m, v)."""
    bc1 = 1.0 - b1 ** step
    bc2 = 1.0 - b2 ** step
    out = ({}, {}, {})
    for k in TRAINABLE:
        mk = b1 * m[k] + (1 - b1) * grads[k]
        vk = b2 * v[k] + (1 - b2) * grads[k] * grads[k]
        out[0][k] = tr[k] - lrs[k] * (mk / bc1) / (jnp.sqrt(vk / bc2) + eps)
        out[1][k], out[2][k] = mk, vk
    return out

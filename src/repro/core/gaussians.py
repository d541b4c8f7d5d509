"""Gaussian primitive parameterisation (3D-GS, Kerbl et al. 2023).

A scene is a fixed-capacity buffer of Gaussians with an ``active`` mask —
fixed shapes keep every training step jit-compatible; densify/prune edit the
mask and free slots rather than reallocating (DESIGN.md §3).

Parameterisation (trainable, unconstrained):
  means    (N, 3)      world-space centers
  log_scales (N, 3)    exp() -> per-axis std dev
  quats    (N, 4)      normalised on use -> rotation
  opacity_logit (N,)   sigmoid() -> alpha in (0,1)
  colors   (N, 3)      SH degree-0 (isosurface splats are view-independent;
                       DESIGN.md §8); sigmoid() -> rgb
plus non-trainable:
  active   (N,) bool
  owner    (N,) int32  spatial partition that owns this gaussian (ghosts carry
                       their *source* partition id -> merge dedupe)
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


class Gaussians(NamedTuple):
    means: jax.Array
    log_scales: jax.Array
    quats: jax.Array
    opacity_logit: jax.Array
    colors: jax.Array
    active: jax.Array
    owner: jax.Array

    @property
    def capacity(self) -> int:
        return self.means.shape[0]

    def trainable(self):
        return {
            "means": self.means,
            "log_scales": self.log_scales,
            "quats": self.quats,
            "opacity_logit": self.opacity_logit,
            "colors": self.colors,
        }

    def with_trainable(self, t):
        return self._replace(
            means=t["means"],
            log_scales=t["log_scales"],
            quats=t["quats"],
            opacity_logit=t["opacity_logit"],
            colors=t["colors"],
        )


def from_points(points, colors=None, *, capacity=None, init_scale=None,
                owner_id=0, opacity=0.6):
    """Initialise one Gaussian per point (paper: isosurface point cloud ->
    initial primitives). init_scale defaults to mean nearest-neighbour-ish
    spacing estimated from the bounding box and point count."""
    n = points.shape[0]
    capacity = capacity or n
    assert capacity >= n
    if init_scale is None:
        bbox = points.max(0) - points.min(0)
        vol = jnp.maximum(jnp.prod(bbox), 1e-12)
        init_scale = (vol / max(n, 1)) ** (1.0 / 3.0)
    pad = capacity - n

    def padded(x, fill=0.0):
        return jnp.concatenate(
            [x, jnp.full((pad,) + x.shape[1:], fill, x.dtype)], axis=0
        ) if pad else x

    means = padded(points.astype(jnp.float32))
    log_scales = jnp.full((capacity, 3), jnp.log(init_scale), jnp.float32)
    quats = jnp.tile(jnp.array([1.0, 0, 0, 0], jnp.float32), (capacity, 1))
    op = jnp.full((capacity,), jnp.log(opacity / (1 - opacity)), jnp.float32)
    if colors is None:
        colors = jnp.full((n, 3), 0.0, jnp.float32)  # sigmoid(0)=0.5 grey
    else:
        colors = jnp.log(jnp.clip(colors, 1e-4, 1 - 1e-4) /
                         (1 - jnp.clip(colors, 1e-4, 1 - 1e-4)))
    colors = padded(colors.astype(jnp.float32))
    active = jnp.concatenate([jnp.ones((n,), bool), jnp.zeros((pad,), bool)])
    owner = jnp.full((capacity,), owner_id, jnp.int32)
    return Gaussians(means, log_scales, quats, op, colors, active, owner)


def quat_to_rotmat(q):
    """(..., 4) normalised-on-use quaternion -> (..., 3, 3)."""
    q = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
    w, x, y, z = q[..., 0], q[..., 1], q[..., 2], q[..., 3]
    return jnp.stack(
        [
            jnp.stack([1 - 2 * (y * y + z * z), 2 * (x * y - w * z), 2 * (x * z + w * y)], -1),
            jnp.stack([2 * (x * y + w * z), 1 - 2 * (x * x + z * z), 2 * (y * z - w * x)], -1),
            jnp.stack([2 * (x * z - w * y), 2 * (y * z + w * x), 1 - 2 * (x * x + y * y)], -1),
        ],
        axis=-2,
    )


def small_matmul(a, b):
    """(..., m, k) x (..., k, n) -> (..., m, n) for tiny m, k, n, as an
    elementwise multiply-and-sum: exact f32 on every backend (a TPU dot
    defaults to bf16 passes) and free of the dot's (k, n)-minor layout,
    which pads each tiny matrix of a splat table to a full (8, 128) tile."""
    return (a[..., :, :, None] * b[..., None, :, :]).sum(-2)


def covariance3d(log_scales, quats):
    """Sigma = R S S^T R^T, (..., 3, 3)."""
    R = quat_to_rotmat(quats)
    S = jnp.exp(log_scales)
    RS = R * S[..., None, :]
    return small_matmul(RS, jnp.swapaxes(RS, -1, -2))

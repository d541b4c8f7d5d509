"""EWA projection of 3D Gaussians to screen space (Zwicker EWA splatting, as
used by 3D-GS) + frustum culling.

Output per gaussian: 2D mean (pixels), 2D covariance (2x2 via [a,b,c] packed),
depth, rgb, alpha, valid flag.  This "projected splat" table is the small
representation that Grendel-style parallelism all-gathers between the
gaussian-parallel and pixel-parallel stages (DESIGN.md §3).

Batch-polymorphic: gaussian fields may carry arbitrary leading dims (the
distributed pipeline batches a partition axis P in front of N).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

from repro.core.cameras import Camera
from repro.core.gaussians import Gaussians, covariance3d, small_matmul
from repro.core.trace import scope

# anti-aliasing dilation as in 3D-GS reference (0.3 px)
COV2D_DILATE = 0.3


class Splats2D(NamedTuple):
    mean2d: jax.Array     # (..., 2) pixel coords
    cov2d: jax.Array      # (..., 3) packed [a, b, c] of [[a, b], [b, c]]
    depth: jax.Array      # (...,)
    rgb: jax.Array        # (..., 3) in [0,1]
    alpha: jax.Array      # (...,)
    radius: jax.Array     # (...,) conservative pixel radius
    valid: jax.Array      # (...,) bool


@scope("project")
def project(g: Gaussians, cam: Camera, *, near: float = 0.05,
            alpha_min: float = 1.0 / 255.0) -> Splats2D:
    """Project all gaussians for one camera. Fully vectorised over leading dims."""
    R = cam.view[:3, :3]
    t = cam.view[:3, 3]
    p_cam = (g.means[..., None, :] * R).sum(-1) + t   # (..., 3), looks +z
    x = p_cam[..., 0]
    y = p_cam[..., 1]
    z = p_cam[..., 2]
    zc = jnp.maximum(z, near)
    u = cam.fx * x / zc + cam.cx
    v = cam.fy * y / zc + cam.cy

    # Jacobian of perspective projection (EWA affine approximation)
    zero = jnp.zeros_like(zc)
    J = jnp.stack(
        [
            jnp.stack([cam.fx / zc, zero, -cam.fx * x / (zc * zc)], -1),
            jnp.stack([zero, cam.fy / zc, -cam.fy * y / (zc * zc)], -1),
        ],
        axis=-2,
    )                                             # (..., 2, 3)
    cov3 = covariance3d(g.log_scales, g.quats)    # (..., 3, 3)
    T = small_matmul(J, R)                        # (..., 2, 3)
    cov2 = small_matmul(small_matmul(T, cov3),
                        jnp.swapaxes(T, -1, -2))  # (..., 2, 2)
    a = cov2[..., 0, 0] + COV2D_DILATE
    b = cov2[..., 0, 1]
    c = cov2[..., 1, 1] + COV2D_DILATE

    det = a * c - b * b
    mid = 0.5 * (a + c)
    lam1 = mid + jnp.sqrt(jnp.maximum(mid * mid - det, 1e-9))
    radius = jnp.ceil(3.0 * jnp.sqrt(jnp.maximum(lam1, 1e-9)))

    alpha = jax.nn.sigmoid(g.opacity_logit)
    rgb = jax.nn.sigmoid(g.colors)

    inside = (
        (z > near)
        & (u + radius > 0) & (u - radius < cam.width)
        & (v + radius > 0) & (v - radius < cam.height)
    )
    valid = inside & g.active & (alpha > alpha_min) & (det > 1e-12)
    return Splats2D(
        mean2d=jnp.stack([u, v], -1),
        cov2d=jnp.stack([a, b, c], -1),
        depth=z,
        rgb=rgb,
        alpha=alpha,
        radius=radius,
        valid=valid,
    )

"""Image quality metrics: PSNR, SSIM (+ masked variants), D-SSIM loss term.

LPIPS requires a pretrained VGG (unavailable offline) — DESIGN.md §8 documents
the substitution: we report PSNR/SSIM everywhere the paper does and a
gradient-similarity proxy (``grad_sim``) where the paper reports LPIPS.
"""

from __future__ import annotations


import jax.numpy as jnp
from jax import lax


def psnr(a, b, mask=None):
    """a, b: (..., H, W, C) in [0, 1]."""
    se = (a.astype(jnp.float32) - b.astype(jnp.float32)) ** 2
    if mask is None:
        mse = se.mean()
    else:
        m = mask.astype(jnp.float32)[..., None]
        mse = (se * m).sum() / jnp.maximum(m.sum() * se.shape[-1], 1.0)
    return 10.0 * jnp.log10(1.0 / jnp.maximum(mse, 1e-12))


def _gaussian_window(size: int = 11, sigma: float = 1.5):
    x = jnp.arange(size, dtype=jnp.float32) - (size - 1) / 2.0
    g = jnp.exp(-(x**2) / (2 * sigma**2))
    g = g / g.sum()
    return jnp.outer(g, g)


def _filter2d(img, win):
    """img: (H, W, C); win: (k, k) -> same-size 'valid-centred' conv (SAME)."""
    k = win.shape[0]
    x = img.transpose(2, 0, 1)[:, None]                     # (C,1,H,W)
    w = win[None, None]                                     # (1,1,k,k)
    y = lax.conv_general_dilated(
        x, w, window_strides=(1, 1), padding=[(k // 2, k // 2)] * 2,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        precision=lax.Precision.HIGHEST,    # f32, not TPU bf16 passes
    )
    return y[:, 0].transpose(1, 2, 0)


def ssim_map(a, b, *, win_size: int = 11, sigma: float = 1.5):
    """Per-pixel SSIM map, (H, W, C) inputs in [0,1] -> (H, W, C)."""
    c1, c2 = 0.01**2, 0.03**2
    a = a.astype(jnp.float32)
    b = b.astype(jnp.float32)
    win = _gaussian_window(win_size, sigma)
    mu_a = _filter2d(a, win)
    mu_b = _filter2d(b, win)
    mu_aa, mu_bb, mu_ab = mu_a * mu_a, mu_b * mu_b, mu_a * mu_b
    s_aa = _filter2d(a * a, win) - mu_aa
    s_bb = _filter2d(b * b, win) - mu_bb
    s_ab = _filter2d(a * b, win) - mu_ab
    return ((2 * mu_ab + c1) * (2 * s_ab + c2)) / (
        (mu_aa + mu_bb + c1) * (s_aa + s_bb + c2)
    )


def ssim(a, b, mask=None, **kw):
    m = ssim_map(a, b, **kw)
    if mask is None:
        return m.mean()
    w = mask.astype(jnp.float32)[..., None]
    return (m * w).sum() / jnp.maximum(w.sum() * m.shape[-1], 1.0)


def d_ssim(a, b, mask=None, **kw):
    """3D-GS loss term: (1 - SSIM) / 2."""
    return (1.0 - ssim(a, b, mask=mask, **kw)) / 2.0


def grad_sim(a, b, mask=None):
    """LPIPS stand-in (documented proxy): 1 - cosine similarity of image
    gradients, lower is better, in [0, 2]."""
    def grads(x):
        x = x.astype(jnp.float32).mean(-1)
        gx = x[:, 1:] - x[:, :-1]
        gy = x[1:, :] - x[:-1, :]
        return gx[:-1], gy[:, :-1]

    ax, ay = grads(a)
    bx, by = grads(b)
    if mask is not None:
        m = mask.astype(jnp.float32)[:-1, :-1]
        ax, ay, bx, by = ax * m, ay * m, bx * m, by * m
    num = (ax * bx + ay * by).sum()
    den = jnp.sqrt((ax**2 + ay**2).sum() * (bx**2 + by**2).sum())
    return 1.0 - num / jnp.maximum(den, 1e-12)

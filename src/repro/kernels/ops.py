"""Jit'd public entry points for the rasterizer kernel.

``rasterize_tiles(feats, origins, tile_h=, tile_w=, impl=)``:

  impl="pallas"   pl.pallas_call kernels (custom_vjp: analytic backward)
  impl="ref"      pure-jnp oracle (jax autodiff) — CPU training path
  impl="interpret" pallas kernels in interpret mode (kernel-body validation
                  on CPU; used by tests)
  impl="auto"     "pallas" on TPU, "ref" otherwise

All impls share semantics exactly (see kernels/ref.py) so swapping impl never
changes training math beyond float-associativity noise.

Three dispatch shapes share these kernels:

  rasterize_tiles          one (T,) grid launch at a single static K
  rasterize_tiles_batched  view-batched: (V, T) flattened to one (V*T,) launch
  rasterize_tiles_tiered   variable-K: one launch per occupancy tier (each at
                           its own K_i over its own compacted tile list),
                           scattered back into the full flat tile image

Each entry point runs under the ``gs.raster`` device scope
(``core.trace``), backward launches included.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import rasterize as rk
from repro.kernels import ref as ref_impl


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _rasterize_pallas(feats, origins, tile_h, tile_w, interpret):
    return rk.rasterize_fwd(feats, origins, tile_h=tile_h, tile_w=tile_w,
                            interpret=interpret)


def _pallas_fwd(feats, origins, tile_h, tile_w, interpret):
    out = rk.rasterize_fwd(feats, origins, tile_h=tile_h, tile_w=tile_w,
                           interpret=interpret)
    return out, (feats, origins, out)


def _pallas_bwd(tile_h, tile_w, interpret, res, gout):
    feats, origins, out = res
    gfeats = rk.rasterize_bwd(feats, origins, out, gout,
                              tile_h=tile_h, tile_w=tile_w,
                              interpret=interpret)
    return gfeats.astype(feats.dtype), jnp.zeros_like(origins)


_rasterize_pallas.defvjp(_pallas_fwd, _pallas_bwd)


def _raster_scope():
    # core imports this module, so the vocabulary is looked up per call
    from repro.core.trace import scope
    return scope("raster")


def resolve_impl(impl: str) -> str:
    if impl != "auto":
        return impl
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def rasterize_tiles(feats, origins, *, tile_h: int, tile_w: int,
                    impl: str = "auto"):
    """feats (T, K, F) -> (T, 4, th, tw) [r, g, b, coverage]. Differentiable
    w.r.t. feats under every impl.

    Mixed-precision boundary: feature blocks may arrive in a reduced
    storage dtype (core.dtypes casts them at the gather/exchange boundary
    under dtype_policy="bf16"); the compositor contract is f32 ACCUMULATION
    regardless, so inputs are promoted here — the single funnel all three
    impls (and the batched/tiered dispatchers below) share, keeping
    ref == interpret == pallas semantics per dtype.  For f32 inputs the
    promote is elided (same-dtype convert), so the default policy compiles
    the exact pre-policy program.  Output is always f32; the backward pass
    rounds the feature cotangents back to the input dtype at this same
    boundary (the transpose of the promote)."""
    impl = resolve_impl(impl)
    with _raster_scope():
        feats = feats.astype(jnp.float32)
        origins = origins.astype(jnp.float32)
        if impl == "ref":
            return ref_impl.rasterize_tiles_ref(feats, origins,
                                                tile_h=tile_h, tile_w=tile_w)
        if impl == "pallas":
            return _rasterize_pallas(feats, origins, tile_h, tile_w, False)
        if impl == "interpret":
            return _rasterize_pallas(feats, origins, tile_h, tile_w, True)
    raise ValueError(impl)


def rasterize_tiles_batched(feats, origins, *, tile_h: int, tile_w: int,
                            impl: str = "auto"):
    """View-batched entry point: feats (V, T, K, F) -> (V, T, 4, th, tw).

    origins may be (T, 2) (shared rig geometry, the common case) or
    (V, T, 2).  The V and T axes are flattened into one (V*T,) kernel grid
    launch — one dispatch for the whole view batch instead of V — and
    unflattened afterwards.  Semantics are identical to V independent
    ``rasterize_tiles`` calls (tiles are independent programs)."""
    V, T, K, F = feats.shape
    with _raster_scope():
        if origins.ndim == 2:
            origins = jnp.broadcast_to(origins[None], (V,) + origins.shape)
        out = rasterize_tiles(
            feats.reshape(V * T, K, F), origins.reshape(V * T, 2),
            tile_h=tile_h, tile_w=tile_w, impl=impl,
        )
        return out.reshape(V, T, 4, tile_h, tile_w)


def rasterize_tiles_tiered(tier_feats, tier_origins, tier_ids, n_tiles: int,
                           *, tile_h: int, tile_w: int, impl: str = "auto"):
    """Variable-K dispatch: one kernel launch per non-empty occupancy tier.

    tier_feats    per tier i: (cap_i, K_i, F) compacted feature tables —
                  each tier carries its OWN static K_i, so sparse tiles pay
                  K_i=16 gather/compute instead of the dense Kmax.
    tier_origins  per tier i: (cap_i, 2) tile origins aligned with the feats.
    tier_ids      per tier i: (cap_i,) int32 flat tile ids (TierPlan.tile_ids
                  from core.tiling.bin_tiles_by_occupancy); slots holding the
                  sentinel ``n_tiles`` are padding and are dropped by the
                  scatter.
    n_tiles       M: the flat tile count of the full image.

    -> (M, 4, th, tw).  Tiles placed in no tier (empty tiles, or overflow
    past the top tier's cap) come back as exact zeros — identical to what
    the kernel produces for an all-alpha-0 list.  Differentiable w.r.t.
    every tier_feats entry: each launch goes through the same custom-VJP
    (pallas/interpret) or autodiff (ref) path as rasterize_tiles, and the
    scatter's transpose routes the per-tier output cotangents back to the
    corresponding tier table (padding slots get zeros via mode="drop").
    Tier capacities are static, so this traces to a fixed launch schedule —
    cap_i == 0 tiers are skipped at trace time ("non-empty tier" dispatch).
    """
    with _raster_scope():
        out = jnp.zeros((n_tiles, 4, tile_h, tile_w), jnp.float32)
        for feats, origins, ids in zip(tier_feats, tier_origins, tier_ids):
            if feats.shape[0] == 0:
                continue
            tiles = rasterize_tiles(feats, origins, tile_h=tile_h,
                                    tile_w=tile_w, impl=impl)
            out = out.at[ids].set(tiles, mode="drop")
        return out

"""Single-device render path: project -> tile-assign -> gather -> kernel ->
untile -> composite.  This is the building block for the trainer, merge, and
ground-truth generation; the multi-device variant (sharding constraints at
each stage) lives in core/distributed.py.

Two rasterizer dispatch modes:

  dense (K=)        every tile carries the same static top-K list — one
                    kernel launch over all T tiles.
  tiered (k_tiers=) tiles are binned by occupancy into K-tiers (e.g.
                    K in {16, 64, 256}); each non-empty tier gets its own
                    launch at its own K, and tier outputs scatter back into
                    the full tile image.  Sparse/background tiles stop
                    paying the dense-K gather+compute, heavy tiles stop
                    truncating at a too-small K.  Exact vs dense at
                    K = k_tiers[-1] whenever the static tier capacities
                    cover the occupancy histogram (see
                    core.tiling.bin_tiles_by_occupancy).
"""

from __future__ import annotations

import functools
from typing import NamedTuple, Optional, Sequence

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.cameras import CAM_VAXES, Camera, select
from repro.core.dtypes import cast_tables
from repro.core.gaussians import Gaussians
from repro.core.projection import project
from repro.core.tiling import (
    DEFAULT_ASSIGN_IMPL,
    NEG,
    SORTED_MIN_TILES,
    TileGrid,
    assign_tiles,
    auto_tier_caps,
    auto_tile_budget,
    bin_tiles_by_occupancy,
    gather_features_at,
    gather_tile_features,
    resolve_assign_impl,
    splat_features,
    splat_tile_counts,
    tile_occupancy,
    tile_origins,
    untile_image,
)
from repro.core.trace import scope
from repro.kernels import rasterize_tiles
from repro.kernels.ops import rasterize_tiles_batched, rasterize_tiles_tiered


class RenderOut(NamedTuple):
    rgb: jax.Array        # (H, W, 3) or (V, H, W, 3), background-composited
    coverage: jax.Array   # (H, W) / (V, H, W) alpha coverage in [0, 1]
    #: tiered renders only: tiles dropped because every tier cap from their
    #: desired tier upward was full (0 when caps cover the scene; scalar, or
    #: (V,) for batched renders).  None on the dense path.
    overflow: Optional[jax.Array] = None
    #: tile-ASSIGNMENT budget counter (scalar; (V,) for batched renders):
    #: bbox candidate slots dropped past the sorted path's static
    #: ``assign_budget`` (coarse pre-cull drops count here too).  Always 0
    #: on the dense sweep.  Separate from ``overflow`` (tier capacities) so
    #: drivers can grow the right static knob — see
    #: ``tiling.grow_tile_budget`` / ``TierSchedule.note_overflow``.
    assign_overflow: Optional[jax.Array] = None


def _gather_feats(g: Gaussians, cam: Camera, grid: TileGrid, *, K: int,
                  coarse: Optional[int], coarse_budget: Optional[int],
                  block: int = 4096,
                  assign_impl: str = DEFAULT_ASSIGN_IMPL,
                  assign_budget: Optional[int] = None,
                  dtype_policy: str = "f32"):
    """Shared first half of the render: project -> tile-assign (indices
    stop-gradiented: discrete assignment) -> per-tile feature gather.

    -> (tile_feats (T, K, FEAT_DIM), idx (T, K), score (T, K),
    assign_ov () int32 assignment-budget drop counter).

    ``dtype_policy="bf16"`` casts the gathered (T, K, F) feature block to
    bf16 at this boundary (halving the kernel's feature footprint; the
    rasterizer promotes back to f32 at entry and accumulates in f32 —
    core.dtypes contract).  Projection and tile ASSIGNMENT stay f32 under
    every policy: assignment is index bookkeeping, not payload, and
    keeping it exact means the bf16 image differs from the f32 oracle only
    by input rounding — never by a swapped splat list."""
    splats = project(g, cam)
    idx, score, assign_ov = assign_tiles(
        splats, grid, K=K, block=block, coarse=coarse,
        coarse_budget=coarse_budget, impl=assign_impl,
        tile_budget=assign_budget, return_overflow=True)
    idx = lax.stop_gradient(idx)
    score = lax.stop_gradient(score)
    feats = cast_tables(gather_tile_features(splats, idx, score),
                        dtype_policy)
    return feats, idx, score, assign_ov


def _composite(img, bg):
    """(..., H, W, 4) kernel output -> RenderOut over a solid background."""
    cov = img[..., 3]
    rgb = img[..., :3] + (1.0 - cov[..., None]) * bg
    return RenderOut(rgb=rgb, coverage=cov)


# ---------------------------------------------------------------------------
# Tiered (variable-K) dispatch
# ---------------------------------------------------------------------------


def _tiered_tiles(feat, idx, score, grid: TileGrid, *, k_tiers, tier_caps,
                  impl: str):
    """Tier-compact a flat (T, Kmax) assignment and rasterize per tier.

    feat (N, F) differentiable feature table; idx/score (T, Kmax) static
    assignment (already stop-gradiented).  -> (tiles (T, 4, th, tw), plan).
    Each tier's tables are compacted to its static cap with K_i columns —
    the gather volume shrinks together with the kernel work.
    """
    T = grid.n_tiles
    plan = bin_tiles_by_occupancy(tile_occupancy(score), k_tiers, tier_caps)
    origins = tile_origins(grid)
    tier_feats, tier_origins = [], []
    for k, ids in zip(k_tiers, plan.tile_ids):
        idx_k = jnp.take(idx[:, :k], ids, axis=0, mode="fill", fill_value=0)
        sc_k = jnp.take(score[:, :k], ids, axis=0, mode="fill",
                        fill_value=NEG)
        tier_feats.append(gather_features_at(feat, idx_k, sc_k))
        tier_origins.append(jnp.take(origins, ids, axis=0, mode="fill",
                                     fill_value=0.0))
    tiles = rasterize_tiles_tiered(tier_feats, tier_origins, plan.tile_ids,
                                   T, tile_h=grid.tile_h, tile_w=grid.tile_w,
                                   impl=impl)
    return tiles, plan


def _tiered_tiles_batched(feat, idx, score, grid: TileGrid, *, k_tiers,
                          tier_caps, impl: str):
    """View-batched tiered dispatch: bin each view's tiles independently
    (shared static caps), then ONE launch per tier over the flattened
    (V * cap_i,) tier tables — the tiered analogue of
    rasterize_tiles_batched's (V*T,) flattening.

    feat (V, N, F); idx/score (V, T, Kmax) -> (tiles (V, T, 4, th, tw),
    plan with per-view counts/overflow)."""
    V, T = score.shape[0], grid.n_tiles
    M = V * T
    plan = jax.vmap(
        lambda o: bin_tiles_by_occupancy(o, k_tiers, tier_caps)
    )(tile_occupancy(score))
    origins = tile_origins(grid)
    offs = jnp.arange(V, dtype=jnp.int32)[:, None] * T

    def take_rows(arr, ids, fill):
        f = lambda a, i: jnp.take(a, i, axis=0, mode="fill", fill_value=fill)
        return jax.vmap(f)(arr, ids)

    tier_feats, tier_origins, flat_ids = [], [], []
    for k, ids in zip(k_tiers, plan.tile_ids):       # ids (V, cap_i)
        cap = ids.shape[1]
        idx_k = take_rows(idx[:, :, :k], ids, 0)     # (V, cap, k)
        sc_k = take_rows(score[:, :, :k], ids, NEG)
        tf = jax.vmap(gather_features_at)(feat, idx_k, sc_k)
        og = jax.vmap(lambda i: jnp.take(origins, i, axis=0, mode="fill",
                                         fill_value=0.0))(ids)
        tier_feats.append(tf.reshape((V * cap,) + tf.shape[2:]))
        tier_origins.append(og.reshape(V * cap, 2))
        flat_ids.append(jnp.where(ids < T, ids + offs, M).reshape(-1))
    tiles = rasterize_tiles_tiered(tier_feats, tier_origins, flat_ids, M,
                                   tile_h=grid.tile_h, tile_w=grid.tile_w,
                                   impl=impl)
    return tiles.reshape(V, T, 4, grid.tile_h, grid.tile_w), plan


def _resolve_tiers(k_tiers, tier_caps, score):
    """Static (k_tiers, tier_caps) tuples; caps auto-sized from concrete
    occupancy when not given (raises under jit — pass static caps there)."""
    k_tiers = tuple(int(k) for k in k_tiers)
    if tier_caps is None:
        tier_caps = auto_tier_caps(tile_occupancy(score), k_tiers)
    return k_tiers, tuple(int(c) for c in tier_caps)


# ---------------------------------------------------------------------------
# Public render entry points
# ---------------------------------------------------------------------------


def render_tiles(g: Gaussians, cam: Camera, grid: TileGrid, *, K: int = 64,
                 impl: str = "auto", coarse: Optional[int] = None,
                 coarse_budget: Optional[int] = None,
                 k_tiers: Optional[Sequence[int]] = None,
                 tier_caps: Optional[Sequence[int]] = None,
                 assign_impl: str = DEFAULT_ASSIGN_IMPL,
                 assign_budget: Optional[int] = None,
                 dtype_policy: str = "f32"):
    """-> (tiles (T, 4, th, tw), idx (T, K'), score (T, K')).

    Differentiable w.r.t. gaussians (tile index lists are stop-gradiented:
    discrete assignment).  With ``k_tiers`` the assignment runs at
    K' = k_tiers[-1] and the kernel dispatch is tiered (one launch per
    non-empty tier); ``K`` is ignored in that mode.  ``assign_impl``
    selects the tile-assignment algorithm ("auto" default: the sort-based
    scatter on large grids, the dense O(T*N) sweep below the measured
    crossover; "dense"/"sorted" pin one — see core.tiling.assign_tiles)
    and ``assign_budget`` the sorted path's static per-splat tile budget."""
    if k_tiers is None:
        feats, idx, score, _ = _gather_feats(g, cam, grid, K=K, coarse=coarse,
                                             coarse_budget=coarse_budget,
                                             assign_impl=assign_impl,
                                             assign_budget=assign_budget,
                                             dtype_policy=dtype_policy)
        tiles = rasterize_tiles(
            feats, tile_origins(grid),
            tile_h=grid.tile_h, tile_w=grid.tile_w, impl=impl,
        )
        return tiles, idx, score
    tiles, idx, score, _, _ = _render_tiles_tiered(
        g, cam, grid, impl=impl, coarse=coarse, coarse_budget=coarse_budget,
        k_tiers=k_tiers, tier_caps=tier_caps, assign_impl=assign_impl,
        assign_budget=assign_budget, dtype_policy=dtype_policy)
    return tiles, idx, score


def _render_tiles_tiered(g, cam, grid, *, impl, coarse, coarse_budget,
                         k_tiers, tier_caps,
                         assign_impl: str = DEFAULT_ASSIGN_IMPL,
                         assign_budget: Optional[int] = None,
                         dtype_policy: str = "f32"):
    splats = project(g, cam)
    idx, score, assign_ov = assign_tiles(
        splats, grid, K=tuple(k_tiers)[-1],
        coarse=coarse, coarse_budget=coarse_budget,
        impl=assign_impl, tile_budget=assign_budget, return_overflow=True)
    idx = lax.stop_gradient(idx)
    score = lax.stop_gradient(score)
    k_tiers, tier_caps = _resolve_tiers(k_tiers, tier_caps, score)
    # bf16 policy casts the (N, F) feature TABLE (not the per-tier gathers):
    # the tier compaction then moves half the bytes too, matching the
    # distributed path's cast-before-collective placement
    feat = cast_tables(splat_features(splats), dtype_policy)
    tiles, plan = _tiered_tiles(feat, idx, score, grid,
                                k_tiers=k_tiers, tier_caps=tier_caps,
                                impl=impl)
    return tiles, idx, score, plan, assign_ov


def render(g: Gaussians, cam: Camera, grid: TileGrid, *, K: int = 64,
           impl: str = "auto", bg: float = 1.0,
           coarse: Optional[int] = None,
           coarse_budget: Optional[int] = None,
           k_tiers: Optional[Sequence[int]] = None,
           tier_caps: Optional[Sequence[int]] = None,
           assign_impl: str = DEFAULT_ASSIGN_IMPL,
           assign_budget: Optional[int] = None,
           dtype_policy: str = "f32") -> RenderOut:
    """Full-image render with background composite (paper bg is white).

    ``dtype_policy="bf16"`` stores the kernel feature tables in bf16
    (compositing still accumulates f32 — see core.dtypes); "f32" (default)
    is bit-identical to builds that predate the knob.

    ``k_tiers=(16, 64, 256)``-style schedules switch to occupancy-tiered
    rasterization (K is then ignored; K' = k_tiers[-1] bounds per-tile
    depth).  ``tier_caps`` are the static per-tier tile capacities — leave
    None outside jit to auto-size from this scene, pass explicit caps under
    jit.  The returned RenderOut.overflow counts tiles dropped past the top
    tier's cap (0 == the tiered image is exact vs dense at K').

    ``assign_impl``/``assign_budget`` pick the tile-assignment algorithm
    ("auto": sort-based scatter on large grids, dense sweep below the
    crossover; both bit-identical whenever the sorted path's budget covers
    the scene; see core.tiling.assign_tiles)."""
    if k_tiers is None:
        feats, idx, score, assign_ov = _gather_feats(
            g, cam, grid, K=K, coarse=coarse, coarse_budget=coarse_budget,
            assign_impl=assign_impl, assign_budget=assign_budget,
            dtype_policy=dtype_policy)
        tiles = rasterize_tiles(feats, tile_origins(grid),
                                tile_h=grid.tile_h, tile_w=grid.tile_w,
                                impl=impl)
        out = _composite(untile_image(tiles, grid), bg)
        return out._replace(assign_overflow=assign_ov)
    tiles, _, _, plan, assign_ov = _render_tiles_tiered(
        g, cam, grid, impl=impl, coarse=coarse, coarse_budget=coarse_budget,
        k_tiers=k_tiers, tier_caps=tier_caps, assign_impl=assign_impl,
        assign_budget=assign_budget, dtype_policy=dtype_policy)
    out = _composite(untile_image(tiles, grid), bg)
    return out._replace(overflow=plan.overflow, assign_overflow=assign_ov)


def render_batch(g: Gaussians, cams: Camera, grid: TileGrid, *, K: int = 64,
                 impl: str = "auto", bg: float = 1.0,
                 coarse: Optional[int] = None,
                 coarse_budget: Optional[int] = None,
                 assign_block: Optional[int] = None,
                 k_tiers: Optional[Sequence[int]] = None,
                 tier_caps: Optional[Sequence[int]] = None,
                 assign_impl: str = DEFAULT_ASSIGN_IMPL,
                 assign_budget: Optional[int] = None,
                 dtype_policy: str = "f32") -> RenderOut:
    """View-batched render: cams carries a leading V axis on view/fx/fy.

    Projection -> tile assignment -> feature gather are vmapped over the
    view axis, then the Pallas/ref kernel runs ONE flattened (V*T,) grid
    launch instead of V dispatches (the per-view Python loop this replaces).
    Returns rgb (V, H, W, 3) and coverage (V, H, W); matches V sequential
    ``render`` calls to float-associativity tolerance.  Differentiable
    w.r.t. gaussians (the trainer's minibatch-of-views step drives this).

    ``k_tiers`` switches the kernel dispatch to occupancy tiers: each view
    bins its own tiles (shared static ``tier_caps``, which must cover the
    worst view — auto-sized outside jit), and each tier gets one flattened
    (V * cap_i,) launch.  RenderOut.overflow is then (V,) dropped-tile
    counts (all-zero == exact vs the dense path at K = k_tiers[-1]).

    assign_block bounds the tile-assignment sweep's temporaries; under vmap
    those are V-fold, so the auto default shrinks the single-view block by
    V (floored at 1024) to keep the peak footprint roughly view-count
    independent.  ``assign_impl``/``assign_budget`` select the assignment
    algorithm per view (see ``render``); the sorted default ignores
    ``assign_block``/``coarse``.
    """
    V = cams.view.shape[0]
    block = assign_block or max(1024, 4096 // max(V, 1))

    if k_tiers is None:
        def gather_one(cam: Camera):
            out = _gather_feats(g, cam, grid, K=K, coarse=coarse,
                                coarse_budget=coarse_budget, block=block,
                                assign_impl=assign_impl,
                                assign_budget=assign_budget,
                                dtype_policy=dtype_policy)
            return out[0], out[3]

        feats, assign_ov = jax.vmap(
            gather_one, in_axes=(CAM_VAXES,))(cams)            # (V,T,K,F)
        tiles = rasterize_tiles_batched(
            feats, tile_origins(grid),
            tile_h=grid.tile_h, tile_w=grid.tile_w, impl=impl,
        )                                                      # (V, T, 4, ...)
        img = jax.vmap(lambda t: untile_image(t, grid))(tiles)  # (V, H, W, 4)
        return _composite(img, bg)._replace(assign_overflow=assign_ov)

    Kmax = tuple(k_tiers)[-1]

    def gather_one_tiered(cam: Camera):
        splats = project(g, cam)
        idx, score, assign_ov = assign_tiles(
            splats, grid, K=Kmax, block=block,
            coarse=coarse, coarse_budget=coarse_budget,
            impl=assign_impl, tile_budget=assign_budget,
            return_overflow=True)
        return (cast_tables(splat_features(splats), dtype_policy),
                lax.stop_gradient(idx),
                lax.stop_gradient(score), assign_ov)

    feat, idx, score, assign_ov = jax.vmap(
        gather_one_tiered, in_axes=(CAM_VAXES,))(cams)
    k_tiers, tier_caps = _resolve_tiers(k_tiers, tier_caps, score)
    tiles, plan = _tiered_tiles_batched(feat, idx, score, grid,
                                        k_tiers=k_tiers, tier_caps=tier_caps,
                                        impl=impl)
    img = jax.vmap(lambda t: untile_image(t, grid))(tiles)
    return _composite(img, bg)._replace(overflow=plan.overflow,
                                        assign_overflow=assign_ov)


# ---------------------------------------------------------------------------
# Cache-aware entry points (serving): assignment tables as first-class values
# ---------------------------------------------------------------------------


def render_batch_tables(g: Gaussians, cams: Camera, grid: TileGrid,
                        idx, score, *, impl: str = "auto",
                        bg: float = 1.0,
                        dtype_policy: str = "f32") -> RenderOut:
    """View-batched render from a PRECOMPUTED assignment table.

    ``idx``/``score`` (V, T, K) are the tables ``assign_tables_jit``
    extracts (already depth-sorted, NEG marking empty slots).  Projection
    still runs per view — it feeds the differentiable feature gather — but
    ``assign_tiles`` is skipped entirely; the kernel work is the same
    flattened (V*T,) launch as ``render_batch``.

    This is the serving cache's render path for hits AND misses (a miss
    extracts a fresh table first, then renders through here), which is
    what makes a cache hit bit-identical to the cold miss that populated
    it: both render the same table through the same program.  K is the
    table's trailing dim — ``tiling.slice_table`` serves lower ladder
    rungs from one cached Kmax table.
    """
    with scope("gather"):
        feat = jax.vmap(lambda cam: splat_features(project(g, cam)),
                        in_axes=(CAM_VAXES,))(cams)           # (V, N, F)
        feat = cast_tables(feat, dtype_policy)   # bf16 storage (policy)
        idx = lax.stop_gradient(idx)
        score = lax.stop_gradient(score)
        tile_feats = jax.vmap(gather_features_at)(feat, idx, score)
    with scope("raster"):
        tiles = rasterize_tiles_batched(
            tile_feats, tile_origins(grid),
            tile_h=grid.tile_h, tile_w=grid.tile_w, impl=impl)
        img = jax.vmap(lambda t: untile_image(t, grid))(tiles)
        return _composite(img, bg)


@functools.lru_cache(maxsize=64)
def render_tables_jit(grid: TileGrid, impl: str, bg: float,
                      dtype_policy: str = "f32"):
    """Cached jitted ``render_batch_tables`` closure, keyed on the static
    render config — INCLUDING the dtype policy, so an f32 and a bf16
    server can never share a compiled program; V / N / table-K variation
    retraces inside the one jit.  The serving batcher's hot path — every
    coalesced request batch dispatches through here with tables from the
    pose-bucket cache."""
    return jax.jit(lambda gg, cc, idx, score: render_batch_tables(
        gg, cc, grid, idx, score, impl=impl, bg=bg,
        dtype_policy=dtype_policy))


@functools.lru_cache(maxsize=64)
def assign_tables_jit(grid: TileGrid, K: int,
                      coarse: Optional[int] = None,
                      assign_impl: str = DEFAULT_ASSIGN_IMPL,
                      assign_budget: Optional[int] = None):
    """Cached jitted assignment-TABLE extraction: ``(g, cams) ->
    (idx (V, T, K), score (V, T, K), assign_ov (V,))``.

    The serving cache's MISS path: extract the per-view (T, K) tables
    once, persist them host-side keyed on the quantized pose bucket
    (``tiling.quantize_pose``), and render every later hit through
    ``render_batch_tables`` without re-assigning.  Keyed on the full
    static assignment config — impl AND budget — so two callers with
    different budgets can never share a compiled table extractor
    (the same contract ``pipeline._render_batch_jit`` keys)."""
    def tables(gg, cc):
        block = max(1024, 4096 // max(cc.view.shape[0], 1))

        def one(cam: Camera):
            splats = project(gg, cam)
            idx, score, ov = assign_tiles(
                splats, grid, K=K, block=block, coarse=coarse,
                impl=assign_impl, tile_budget=assign_budget,
                return_overflow=True)
            return idx, score, ov

        return jax.vmap(one, in_axes=(CAM_VAXES,))(cc)
    return jax.jit(tables)


@functools.lru_cache(maxsize=64)
def tile_count_probe_jit(grid: TileGrid):
    """Cached jitted sorted-budget probe: (gaussians, cams) -> () int32 max
    per-splat bbox tile count over the view batch (gaussian fields may
    carry extra leading dims — the distributed (P, N) layout works too).
    Host layers feed the fetched value to ``tiling.auto_tile_budget`` and
    ``tiling.resolve_assign_impl`` to pick a static sorted-path budget —
    or to demote "auto" back to the dense sweep for big-splat scenes.  A
    jitted global reduction, so every host of a mesh sees the same value.
    """
    def probe(gg, cc):
        one = lambda c: splat_tile_counts(project(gg, c), grid).max()
        return jax.vmap(one, in_axes=(CAM_VAXES,))(cc).max()
    return jax.jit(probe)


def max_tile_count(g: Gaussians, cams: Camera, grid: TileGrid, *,
                   chunk: int = 8) -> int:
    """Host-side max per-splat bbox tile count over a WHOLE camera rig,
    probed in fixed-shape chunks of ``chunk`` views (tail chunks repeat
    the last view) so every rig size shares a handful of compiles and the
    peak probe footprint stays bounded."""
    V = cams.view.shape[0]
    best = 0
    for s in range(0, V, chunk):
        vi = jnp.clip(jnp.arange(s, s + chunk), 0, V - 1)
        best = max(best,
                   int(tile_count_probe_jit(grid)(g, select(cams, vi))))
    return best


def resolve_assignment(g: Gaussians, cams: Camera, grid: TileGrid, *,
                       assign_impl: str = DEFAULT_ASSIGN_IMPL,
                       assign_budget: Optional[int] = None):
    """Host-side resolution of the tile-assignment knobs -> a concrete
    ``(impl, budget)`` pair ready for a jitted render/train step.

    The one shared probe-and-resolve policy for every host loop
    (pipeline.render_views, train.fit_partition,
    distributed.fit_partitions): when the sorted path is in play
    ("sorted" pinned, or "auto" on a >= SORTED_MIN_TILES grid) and no
    budget was given, measure the max per-splat bbox tile count over the
    WHOLE rig (not just the first minibatch — a later close-up view must
    not outgrow the budget silently) and size a static budget with slack
    via ``tiling.auto_tile_budget``; then let
    ``tiling.resolve_assign_impl`` decide, demoting "auto" back to the
    always-exact dense sweep when the probed/explicit budget is too fat
    for duplicate-and-sort to win.  Callers re-resolve after every
    densify (radii are trained parameters).  Works on sharded (P, N)
    gaussians: the probe is a jitted global max, identical on every host.
    """
    candidate = (assign_impl == "sorted"
                 or (assign_impl == "auto"
                     and grid.n_tiles >= SORTED_MIN_TILES))
    if assign_budget is None and candidate:
        assign_budget = auto_tile_budget(max_tile_count(g, cams, grid),
                                         grid.n_tiles)
    impl = resolve_assign_impl(assign_impl, grid.n_tiles, assign_budget)
    return impl, (assign_budget if impl == "sorted" else None)


@functools.lru_cache(maxsize=64)
def occupancy_probe_jit(grid: TileGrid, K: int, coarse: Optional[int] = None,
                        assign_impl: str = DEFAULT_ASSIGN_IMPL,
                        assign_budget: Optional[int] = None):
    """Cached jitted ``view_occupancy`` closure — the standard occupancy
    probe for tier-cap sizing (``TierSchedule.probe`` input).  Shared by
    pipeline.render_views and train.fit_partition so the same (grid, K,
    coarse, assign_impl, assign_budget) probe compiles once.  The probe
    must use the same assignment impl/budget as the step it sizes caps for
    (occupancy is exact either way when nothing overflows, but budgets
    truncate consistently only within one impl)."""
    return jax.jit(lambda gg, cc: view_occupancy(
        gg, cc, grid, K=K, coarse=coarse, assign_impl=assign_impl,
        assign_budget=assign_budget))


def view_occupancy(g: Gaussians, cams: Camera, grid: TileGrid, *, K: int,
                   coarse: Optional[int] = None,
                   coarse_budget: Optional[int] = None,
                   assign_block: Optional[int] = None,
                   assign_impl: str = DEFAULT_ASSIGN_IMPL,
                   assign_budget: Optional[int] = None):
    """(V, T) int32 per-view tile occupancy at assignment depth K.

    The cheap prepass pipeline.render_views uses to auto-size static tier
    caps once per gaussian set before entering the cached tiered jit.
    assign_block defaults to the same V-shrunk block as render_batch so the
    vmapped sweep's temporaries stay view-count independent; callers with
    many views should additionally chunk the view axis (render_views does)."""
    V = cams.view.shape[0]
    block = assign_block or max(1024, 4096 // max(V, 1))

    def one(cam: Camera):
        splats = project(g, cam)
        _, score = assign_tiles(splats, grid, K=K, block=block,
                                coarse=coarse, coarse_budget=coarse_budget,
                                impl=assign_impl, tile_budget=assign_budget)
        return tile_occupancy(score)

    return jax.vmap(one, in_axes=(CAM_VAXES,))(cams)

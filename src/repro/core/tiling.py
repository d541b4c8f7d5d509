"""TPU-adapted tile assignment: fixed-K per-tile gaussian lists.

GPU 3D-GS builds variable-length per-tile lists by radix-sorting (tile|depth)
keys with atomics.  On TPU we keep the top-K *front-most* gaussians per tile
(conservative circle/rect overlap test), built as a blockwise running top-k —
dense, regular compute, no atomics/sort (DESIGN.md §3).  K >= the local
overlap depth makes this exact; tests validate the approximation.

The resulting (T, K) index lists come out depth-sorted (top-k on -depth,
ties broken by splat index so every merge order yields the same list), which
is exactly the order front-to-back compositing needs.

Tiles are rectangular: the TPU-native shape is (8, 128) — one VREG row of
pixels per compositing step (DESIGN.md §3) — while CPU tests use small tiles.

Shape-contract glossary (used across tiling/render/kernels docstrings):
  N  gaussians in the (projected) splat table
  T  image tiles (grid.n_tiles); M for a generic flat tile axis
  K  per-tile splat-list depth; Kmax = the largest tier when tiered
  V  views in a batched render
  S  superblocks in the coarse pre-cull

Variable-K tiers: ``bin_tiles_by_occupancy`` groups tiles into K-tiers
(e.g. K in {16, 64, 256}) by their live-entry count so the rasterizer can
launch one kernel per tier instead of paying the max K everywhere; see
``TierPlan`` and kernels/ops.rasterize_tiles_tiered.
"""

from __future__ import annotations

from typing import NamedTuple, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.projection import Splats2D
from repro.core.trace import scope

NEG = -1e30

#: per-splat feature vector length fed to the rasterizer kernel
#: [mx, my, conicA, conicB, conicC, r, g, b, alpha, pad...] — padded to 16 so
#: the (K, F) VMEM block rows are power-of-two aligned.
FEAT_DIM = 16


class TileGrid(NamedTuple):
    """Static image/tile geometry: (height, width) pixels split into
    row-major (tile_h, tile_w) tiles — T = n_tiles = ny * nx.  Hashable, so
    it can key jit caches (pipeline._render_batch_jit) and be closed over
    as a static argument."""
    width: int
    height: int
    tile_h: int = 8
    tile_w: int = 128

    @property
    def nx(self) -> int:
        return (self.width + self.tile_w - 1) // self.tile_w

    @property
    def ny(self) -> int:
        return (self.height + self.tile_h - 1) // self.tile_h

    @property
    def n_tiles(self) -> int:
        return self.nx * self.ny


def tile_bounds(grid: TileGrid):
    """Tile rects: (T, 2) lo, (T, 2) hi in pixel coords (x, y)."""
    ty, tx = jnp.meshgrid(
        jnp.arange(grid.ny), jnp.arange(grid.nx), indexing="ij"
    )
    lo = jnp.stack(
        [tx.reshape(-1) * grid.tile_w, ty.reshape(-1) * grid.tile_h], -1
    )
    hi = lo + jnp.array([grid.tile_w, grid.tile_h])
    return lo.astype(jnp.float32), hi.astype(jnp.float32)


def tile_origins(grid: TileGrid):
    """(T, 2) float32 pixel coords of each tile's top-left corner (x, y)."""
    lo, _ = tile_bounds(grid)
    return lo


def topk_by_score_then_index(cat_s, cat_i, K: int):
    """Top-K of (score, idx) pairs: score descending, splat index ascending.

    cat_s (..., C) float32 scores, cat_i (..., C) int32 indices ->
    (..., K) of each.  The secondary index key makes the selection a pure
    function of the (score, idx) SET — any blockwise/strip-wise merge order
    (dense sweep, coarse survivors, distributed tile strips) lands on the
    same K entries even when scores tie at the boundary, which is what keeps
    single-device and distributed assignment bit-identical (ROADMAP
    tie-break divergence item).

    Implemented with lax.top_k, which breaks value ties by the LOWER input
    position (the chlo.top_k contract; ~30x cheaper on CPU than an explicit
    two-key lax.sort over the (K + block)-wide merge).  Positional ties
    equal index-order ties under one PRECONDITION every caller satisfies:
    within any run of equal scores, cat_i must be ascending.  The blockwise
    scans guarantee it structurally — the carry holds only earlier
    (lower-index) blocks and is inductively index-sorted within ties, and
    each block's candidates are generated in index order (coarse candidate
    lists and strip-compacted tables preserve table order too).  The
    merge-order-invariance test in test_tiling_properties.py pins this
    against backend regressions.
    """
    new_s, sel = lax.top_k(cat_s, K)
    return new_s, jnp.take_along_axis(cat_i.astype(jnp.int32), sel,
                                      axis=-1)


def _merge_block_topk(top_s, top_i, score, b0, K: int):
    """One step of the dense sweep: merge a block of CONTIGUOUS splats into
    the running top-K.

    top_s/top_i (..., K) the carry, score (..., block) the scores of splats
    b0 .. b0 + block - 1 -> (new_s, new_i) (..., K), bit-identical to
    ``topk_by_score_then_index`` on the concatenation [carry | block] (the
    same lax.top_k selection, empty slots included) without building or
    gathering from the (K + block)-wide index row: a block winner at merged
    position p >= K is splat b0 + p - K, and a carried winner is read out
    of the K carried slots by compare-and-select.  On TPU that gather runs
    element by element and costs about twice the top_k itself.
    """
    new_s, sel = lax.top_k(jnp.concatenate([top_s, score], axis=-1), K)
    slots = jnp.arange(K, dtype=jnp.int32)
    carried = jnp.where(sel[..., :, None] == slots, top_i[..., None, :],
                        0).sum(axis=-1, dtype=jnp.int32)
    return new_s, jnp.where(sel < K, carried, b0 + sel - K)


# ---------------------------------------------------------------------------
# Coarse superblock pre-cull
# ---------------------------------------------------------------------------


def superblock_bounds(grid: TileGrid, sb: int):
    """Bounds of sb x sb tile superblocks: (S, 2) lo / hi pixel rects.

    The last row/column of superblocks may extend past the image — harmless,
    the coarse test is conservative (a superset of true tile overlaps).
    """
    sx = (grid.nx + sb - 1) // sb
    sy = (grid.ny + sb - 1) // sb
    syi, sxi = jnp.meshgrid(jnp.arange(sy), jnp.arange(sx), indexing="ij")
    lo = jnp.stack(
        [sxi.reshape(-1) * grid.tile_w * sb, syi.reshape(-1) * grid.tile_h * sb],
        -1,
    ).astype(jnp.float32)
    hi = lo + jnp.array([grid.tile_w * sb, grid.tile_h * sb], jnp.float32)
    return lo, hi


def coarse_candidates(mean2d, radius, valid, grid: TileGrid, *, sb: int,
                      budget: int, block: int = 4096):
    """Per-superblock candidate splat lists via one cheap circle/rect pass.

    -> (cand (S, budget) int32, overflow () int32).  ``cand`` holds indices
    into the splat table; slots past the true per-superblock occupancy hold
    N (one-past-the-end sentinel).  If a superblock's occupancy exceeds
    ``budget``, the HIGHEST-INDEXED splats overflow and are dropped — table
    order, not depth order, so the loss is arbitrary w.r.t. visibility.
    ``overflow`` counts exactly those dropped (superblock, splat) candidate
    pairs; 0 means the cull was exact.  Callers must size the budget to the
    scene (assign_tiles' auto budget is documented there; budget >=
    occupancy makes the cull exact) and should monitor the counter in
    production instead of trusting the budget blindly.

    Blockwise over gaussians like the dense sweep — O(S * block)
    temporaries, not O(S * N) — carrying per-superblock running counts so
    each block's hits compact to their final columns with one cumsum + one
    scatter (a vmapped size-bounded nonzero costs ~3x the whole dense
    assignment sweep on CPU).
    """
    lo, hi = superblock_bounds(grid, sb)             # (S, 2)
    N = mean2d.shape[0]
    S = lo.shape[0]
    block = min(block, max(N, 1))
    nb = (N + block - 1) // block
    Np = nb * block

    pad = lambda x, fill: jnp.pad(x, (0, Np - N), constant_values=fill)
    mx = pad(mean2d[:, 0], 0.0).reshape(nb, block)
    my = pad(mean2d[:, 1], 0.0).reshape(nb, block)
    rd = pad(radius, 0.0).reshape(nb, block)
    vd = pad(valid, False).reshape(nb, block)        # padded rows never hit
    idxb = jnp.arange(Np, dtype=jnp.int32).reshape(nb, block)

    rows = jnp.arange(S)[:, None]

    def body(carry, x):
        count, cand = carry                          # (S,), (S, budget+1)
        bmx, bmy, brd, bvd, bidx = x
        cx = jnp.clip(bmx[None, :], lo[:, :1], hi[:, :1])     # (S, block)
        cy = jnp.clip(bmy[None, :], lo[:, 1:], hi[:, 1:])
        dx = bmx[None, :] - cx
        dy = bmy[None, :] - cy
        hit = ((dx * dx + dy * dy) <= (brd * brd)[None, :]) & bvd[None, :]
        # overflow (and non-hits) land in scratch column ``budget`` ->
        # sliced off below
        pos = jnp.where(hit, count[:, None] + jnp.cumsum(hit, axis=1) - 1,
                        budget)
        pos = jnp.minimum(pos, budget)
        cand = cand.at[rows, pos].set(jnp.broadcast_to(bidx, hit.shape),
                                      mode="drop")
        return (count + hit.sum(axis=1), cand), None

    init = (jnp.zeros((S,), jnp.int32),
            jnp.full((S, budget + 1), N, jnp.int32))
    (count, cand), _ = lax.scan(body, init, (mx, my, rd, vd, idxb))
    overflow = jnp.maximum(count - budget, 0).sum().astype(jnp.int32)
    return cand[:, :budget], overflow


def _coarse_budget(N: int, S: int, K: int, budget) -> int:
    """Resolve the per-superblock candidate budget (see assign_tiles)."""
    if budget is None:
        # auto budget: 4x headroom over uniform splat->superblock occupancy.
        # On coarse grids (S < 8) the radius halo rivals the superblock size
        # and the uniform model breaks down — fall back to exact (budget=N).
        budget = N if S < 8 else max(4 * K, -(-4 * N // S))
    budget = min(max(int(budget), K), N)
    budget = -(-budget // 128) * 128 if budget >= 128 else budget
    return min(budget, N)


def _assign_tiles_coarse(splats: Splats2D, grid: TileGrid, *, K: int,
                         block: int, sb: int, budget: int):
    """Exact circle/rect top-K restricted to coarse-pass survivors.

    Same contract as assign_tiles (returns (idx, score, overflow)); work
    drops from O(T*N) to O(S*N + T*budget) where S = T / sb^2.  Candidate
    features are gathered ONCE per superblock (gather volume S*budget rows,
    not T*budget) and the fine test runs superblock-major over (S, sb^2
    tile slots, block) panes, scattered back to row-major tile order at the
    end.
    """
    N = splats.mean2d.shape[0]
    sx = (grid.nx + sb - 1) // sb
    sy = (grid.ny + sb - 1) // sb
    S, sb2 = sx * sy, sb * sb

    cand, overflow = coarse_candidates(splats.mean2d, splats.radius,
                                       splats.valid, grid, sb=sb,
                                       budget=budget,
                                       block=block)            # (S, M)
    M = cand.shape[1]
    cb = min(block, M)
    nb = (M + cb - 1) // cb
    cand = jnp.pad(cand, ((0, 0), (0, nb * cb - M)), constant_values=N)

    # one gather per field per superblock; sentinel N -> fill (invalid)
    take = lambda arr, fill: jnp.take(arr, cand, axis=0, mode="fill",
                                      fill_value=fill)
    mean_c = take(splats.mean2d, 0.0)                # (S, Mp, 2)
    rad_c = take(splats.radius, 0.0)
    depth_c = take(splats.depth, 1e30)
    valid_c = take(splats.valid, False)

    # tile-slot rects per superblock, (S, sb2, 2); slots past the image edge
    # are dead weight (sliced away by the scatter-back below)
    syi, sxi = jnp.meshgrid(jnp.arange(sy), jnp.arange(sx), indexing="ij")
    jy, jx = jnp.meshgrid(jnp.arange(sb), jnp.arange(sb), indexing="ij")
    ty = syi.reshape(-1, 1) * sb + jy.reshape(-1)    # (S, sb2)
    tx = sxi.reshape(-1, 1) * sb + jx.reshape(-1)
    lo_sb = jnp.stack([tx * grid.tile_w, ty * grid.tile_h], -1) \
        .astype(jnp.float32)
    hi_sb = lo_sb + jnp.array([grid.tile_w, grid.tile_h], jnp.float32)

    xs = (mean_c.reshape(S, nb, cb, 2).transpose(1, 0, 2, 3),
          rad_c.reshape(S, nb, cb).transpose(1, 0, 2),
          depth_c.reshape(S, nb, cb).transpose(1, 0, 2),
          valid_c.reshape(S, nb, cb).transpose(1, 0, 2),
          cand.reshape(S, nb, cb).transpose(1, 0, 2))

    def body(carry, x):
        top_score, top_idx = carry                   # (S, sb2, K)
        mb, rb, db, vb, ci = x                       # (S, cb, ...)
        cx = jnp.clip(mb[:, None, :, 0], lo_sb[..., :1], hi_sb[..., :1])
        cy = jnp.clip(mb[:, None, :, 1], lo_sb[..., 1:], hi_sb[..., 1:])
        dx = mb[:, None, :, 0] - cx                  # (S, sb2, cb)
        dy = mb[:, None, :, 1] - cy
        hit = (dx * dx + dy * dy) <= (rb * rb)[:, None, :]
        score = jnp.where(hit & vb[:, None, :], -db[:, None, :], NEG)
        cat_s = jnp.concatenate([top_score, score], axis=-1)
        cat_i = jnp.concatenate(
            [top_idx, jnp.broadcast_to(ci[:, None, :].astype(jnp.int32),
                                       score.shape)], axis=-1)
        new_s, new_i = topk_by_score_then_index(cat_s, cat_i, K)
        return (new_s, new_i), None

    init = (jnp.full((S, sb2, K), NEG, jnp.float32),
            jnp.zeros((S, sb2, K), jnp.int32))
    (score_s, idx_s), _ = lax.scan(body, init, xs)

    # scatter back: tile t (row-major) lives at slot (sbid, (ty%sb)*sb+tx%sb)
    tyf, txf = jnp.meshgrid(jnp.arange(grid.ny), jnp.arange(grid.nx),
                            indexing="ij")
    pos = ((tyf // sb) * sx + txf // sb) * sb2 + (tyf % sb) * sb + txf % sb
    pos = pos.reshape(-1)                            # (T,)
    score = score_s.reshape(S * sb2, K)[pos]
    idx = idx_s.reshape(S * sb2, K)[pos]
    # map sentinel slots back to a safe in-range index (they carry score NEG)
    idx = jnp.where(score > NEG / 2, idx, 0)
    return idx, score, overflow


@scope("assign")
def assign_tiles(splats: Splats2D, grid: TileGrid, *, K: int = 64,
                 block: int = 4096, coarse: Optional[int] = None,
                 coarse_budget: Optional[int] = None,
                 return_overflow: bool = False, impl: str = "dense",
                 tile_budget: Optional[int] = None):
    """Top-K front-most gaussians per tile.

    Returns (idx (T, K) int32 into the splat table, score (T, K); score==NEG
    marks empty slots).  With ``return_overflow=True`` a third () int32 is
    appended: the number of candidates the assignment dropped past a static
    budget (always 0 on the dense path without ``coarse``) — production
    configs should log it and treat nonzero as "grow the budget".

    ``impl`` selects the assignment algorithm (same contract either way —
    the two are bit-identical whenever no budget overflows, empty slots
    included):

      "auto"    "sorted" when the grid has >= SORTED_MIN_TILES flat tiles
                AND a ``tile_budget`` is in hand and lean enough to win
                (see resolve_assign_impl; the measured CPU crossover is in
                benchmarks/bench_assign.py), "dense" otherwise — what the
                render/train layers default to via ``assign_impl``; their
                host loops probe the budget (render.resolve_assignment).
      "dense"   blockwise O(T * N) sweep: carry a running top-k and merge
                each gaussian block with a two-key sort (score desc, splat
                index asc) — O(T * block) memory; the index tie-break makes
                the result independent of the merge order (see
                _merge_block_topk).  This is the test oracle and the
                escape hatch — always exact, never drops a candidate.
      "sorted"  duplicate-and-sort scatter (``assign_tiles_sorted``): each
                splat expands into its overlapped-tile candidates under a
                static per-splat ``tile_budget``, one global three-key sort
                groups and orders them, and a segmented scatter emits the
                (T, K) layout — O(N * B log(N * B)), independent of T, the
                production default (render/train wire it via
                ``assign_impl``).  ``coarse`` is ignored (the expansion
                already skips non-overlapped tiles).

    ``coarse=sb`` (dense only) enables a two-level cull: a cheap circle/rect
    pass against sb x sb tile superblocks compacts per-superblock candidate
    lists of size ``coarse_budget`` (auto: N when the grid has S < 8
    superblocks, else max(4K, ceil(4N/S)) — 4x headroom over uniform
    occupancy — rounded up to 128), and the exact per-tile test runs only
    against those survivors — O(S*N + T*budget) instead of O(T*N).  With
    budget >= true superblock occupancy the result is identical to the
    dense path on live slots (empty-slot idx values are unspecified in both
    paths); on overflow the highest-INDEXED candidates are dropped
    (arbitrary w.r.t. depth — see coarse_candidates), so size budgets
    generously.  When the resolved budget reaches N the coarse pass cannot
    cull anything, so the dense path runs directly (identical result, none
    of the pre-cull overhead).
    """
    if resolve_assign_impl(impl, grid.n_tiles, tile_budget) == "sorted":
        idx, score, ov = assign_tiles_sorted(splats, grid, K=K,
                                             tile_budget=tile_budget,
                                             return_overflow=True)
        return (idx, score, ov) if return_overflow else (idx, score)
    if coarse is not None and coarse > 1:
        N = splats.mean2d.shape[0]
        S = (((grid.nx + coarse - 1) // coarse)
             * ((grid.ny + coarse - 1) // coarse))
        budget = _coarse_budget(N, S, K, coarse_budget) if N else 0
        if 0 < budget < N:
            idx, score, overflow = _assign_tiles_coarse(
                splats, grid, K=K, block=block, sb=coarse, budget=budget)
            return (idx, score, overflow) if return_overflow else (idx, score)
        # budget >= N (or empty table): fall through to the dense sweep
    lo, hi = tile_bounds(grid)                      # (T, 2)
    N = splats.mean2d.shape[0]
    block = min(block, max(N, K))
    nb = (N + block - 1) // block
    Np = nb * block

    def pad(x, fill=0.0):
        return jnp.pad(x, ((0, Np - N),) + ((0, 0),) * (x.ndim - 1),
                       constant_values=fill)

    mean = pad(splats.mean2d)
    rad = pad(splats.radius)
    depth = pad(splats.depth, 1e30)
    valid = jnp.pad(splats.valid, (0, Np - N), constant_values=False)

    meanb = mean.reshape(nb, block, 2)
    radb = rad.reshape(nb, block)
    depthb = depth.reshape(nb, block)
    validb = valid.reshape(nb, block)

    def body(carry, xs):
        top_score, top_idx = carry                  # (T, K)
        mb, rb, db, vb, b0 = xs
        # circle/rect overlap: clamp center to rect, compare distance to radius
        # plain slices: a None beside an integer index traces as a gather
        mx, my = mb[:, 0][None, :], mb[:, 1][None, :]
        cx = jnp.clip(mx, lo[:, :1], hi[:, :1])             # (T, block)
        cy = jnp.clip(my, lo[:, 1:], hi[:, 1:])
        dx = mx - cx
        dy = my - cy
        hit = (dx * dx + dy * dy) <= (rb * rb)[None, :]
        score = jnp.where(hit & vb[None, :], -db[None, :], NEG)  # (T, block)
        return _merge_block_topk(top_score, top_idx, score, b0, K), None

    T = grid.n_tiles
    init = (jnp.full((T, K), NEG, jnp.float32), jnp.zeros((T, K), jnp.int32))
    b0s = jnp.arange(nb, dtype=jnp.int32) * block
    (score, idx), _ = lax.scan(body, init, (meanb, radb, depthb, validb, b0s))
    if return_overflow:
        return idx, score, jnp.zeros((), jnp.int32)   # dense path never drops
    return idx, score


# ---------------------------------------------------------------------------
# Sort-based assignment (duplicate-and-sort scatter)
# ---------------------------------------------------------------------------


#: default static per-splat tile budget for the sorted assignment path: a
#: 4x4-tile bbox neighbourhood.  The sorted path's work is O(N * B), so the
#: default stays lean; scenes with larger splats (or callers that want
#: provable exactness, budget = T) pass an explicit ``tile_budget`` and
#: watch the overflow counter (0 == nothing was dropped).
DEFAULT_TILE_BUDGET = 16

#: assignment impl the render/train layers default to (``assign_impl=``):
#: "auto" picks the sort-based scatter when the grid is large enough AND a
#: per-splat budget is known to be lean enough for it to win (see
#: resolve_assign_impl; bench_assign measures the crossover) — the host
#: entry points probe that budget from concrete splats, and traced
#: building blocks without one stay on the always-exact dense sweep.
#: "dense"/"sorted" pin one path.
DEFAULT_ASSIGN_IMPL = "auto"

#: "auto" crossover: grids with fewer flat tiles than this stay on the
#: dense sweep (small-T CPU grids — the test tier — where the sweep's
#: T*N work is trivial and the sort constant dominates).
SORTED_MIN_TILES = 512

#: "auto" crossover, per-splat axis: the sorted path's O(N*B) work beats
#: the dense O(T*N) sweep only while B (the per-splat tile budget) stays
#: under ~T / this ratio (measured on CPU: ~20x higher per-element cost
#: for expand+sort vs the sweep's hit test).  Callers that PROBE a budget
#: from concrete splats (render_views / fit_partition / fit_partitions)
#: feed it to resolve_assign_impl so big-splat scenes — where every splat
#: touches ~a hundred tiles — honestly fall back to the sweep.
SORTED_BUDGET_RATIO = 20


def resolve_assign_impl(impl: str, n_tiles: int,
                        tile_budget: Optional[int] = None) -> str:
    """Resolve an ``assign_impl`` knob ("auto" | "dense" | "sorted") to a
    concrete algorithm for a grid with ``n_tiles`` flat tiles.  "auto" is
    resolved from the GLOBAL grid size everywhere (the distributed strip
    assignment resolves on the full grid, not its strip window), so one
    scene picks one algorithm across every execution layout.

    "auto" picks the sorted path only when it can PROVE it should: the
    grid must carry >= SORTED_MIN_TILES flat tiles AND the caller must
    know a per-splat ``tile_budget`` (probed from concrete splats — the
    host entry points render_views / fit_partition(s) do this via
    ``render.resolve_assignment`` — or passed explicitly) that stays under
    n_tiles / SORTED_BUDGET_RATIO.  With no budget in hand (a directly
    jitted building block) "auto" stays on the always-exact dense sweep —
    a silent candidate-dropping default would violate the overflow-counter
    honesty contract; pin ``assign_impl="sorted"`` (and size the budget)
    to force the sorted path there.  Budgets past the ratio demote to
    dense too: scenes of few huge splats are where duplicate-and-sort
    loses."""
    if impl == "auto":
        if n_tiles < SORTED_MIN_TILES or tile_budget is None \
                or tile_budget * SORTED_BUDGET_RATIO > n_tiles:
            return "dense"
        return "sorted"
    if impl not in ("dense", "sorted"):
        raise ValueError(f"unknown assignment impl {impl!r}; expected "
                         "'auto', 'dense' or 'sorted'")
    return impl


def resolve_tile_budget(n_tiles: int, tile_budget: Optional[int]) -> int:
    """Static per-splat budget: auto = min(T, DEFAULT_TILE_BUDGET); clamped
    to [1, T] (a splat can overlap at most all T tiles, where the expansion
    provably cannot drop)."""
    b = DEFAULT_TILE_BUDGET if tile_budget is None else int(tile_budget)
    return max(1, min(b, max(n_tiles, 1)))


def _bbox_bounds(mx, my, rad, grid: TileGrid):
    """Clipped tile-coordinate bbox of each splat's circle: (x0, x1, y0, y1),
    batch-polymorphic over leading dims.  The low edges use ceil-1 (not
    floor) so a circle exactly tangent to a tile boundary still covers the
    tile the dense sweep's clamp test counts as a hit."""
    tw = jnp.float32(grid.tile_w)
    th = jnp.float32(grid.tile_h)
    x0 = jnp.clip(jnp.ceil((mx - rad) / tw).astype(jnp.int32) - 1,
                  0, grid.nx - 1)
    x1 = jnp.clip(jnp.floor((mx + rad) / tw).astype(jnp.int32),
                  0, grid.nx - 1)
    y0 = jnp.clip(jnp.ceil((my - rad) / th).astype(jnp.int32) - 1,
                  0, grid.ny - 1)
    y1 = jnp.clip(jnp.floor((my + rad) / th).astype(jnp.int32),
                  0, grid.ny - 1)
    return x0, x1, y0, y1


def splat_tile_counts(splats: Splats2D, grid: TileGrid):
    """(..., N) int32 per-splat bbox candidate-tile counts — the quantity
    the sorted path's ``tile_budget`` must cover for bit-exactness (and
    what its overflow counter reports when it doesn't).  Batch-polymorphic;
    this is the budget-probe input for host layers (render.
    tile_count_probe_jit -> auto_tile_budget)."""
    x0, x1, y0, y1 = _bbox_bounds(splats.mean2d[..., 0],
                                  splats.mean2d[..., 1], splats.radius, grid)
    cnt = jnp.maximum(x1 - x0 + 1, 0) * jnp.maximum(y1 - y0 + 1, 0)
    return jnp.where(splats.valid, cnt, 0).astype(jnp.int32)


def auto_tile_budget(max_count, n_tiles: int, *, slack: float = 1.5,
                     round_to: int = 16) -> int:
    """CONCRETE max per-splat bbox count -> static sorted-path budget:
    scaled by ``slack`` (splat radii drift between probes — they are
    trained parameters), rounded up to ``round_to`` so nearby probes hash
    to the same jit cache entry, clamped to [1, n_tiles] (where the
    expansion provably cannot drop).  Host-side only — raises under
    tracing, exactly like auto_tier_caps (budgets are static shapes)."""
    _reject_tracers("auto_tile_budget", max_count)
    b = int(np.ceil(max(int(max_count), 1) * slack))
    b = -(-b // round_to) * round_to
    return max(1, min(b, max(int(n_tiles), 1)))


def window_overlap_mask(mx, my, rad, valid, grid: TileGrid, *,
                        t0, n_local: int, t_end=None):
    """Which splats' clipped tile bboxes can touch the contiguous row-major
    flat-tile window ``[t0, t0 + n_local)``.

    mx/my/rad/valid (..., N) splat columns; ``t0`` a (possibly traced)
    scalar window offset or a (W,) vector of offsets (a new leading window
    axis is prepended).  -> bool (..., N) (or (W, ..., N)).

    Same bbox-row arithmetic as ``_expand_splat_tiles``'s window clamp: a
    window is a contiguous row-major tile range, so its tiles live in rows
    ``[t0 // nx, (t0 + n_local - 1) // nx]`` and a splat whose clipped bbox
    rows intersect that span is a SUPERSET of the splats whose circles hit
    any window tile — filtering by this mask provably drops no true hit.
    This is the per-(src, dst)-edge overlap test of the sparse splat
    exchange (core.distributed): each destination's sub-strip is one such
    window.

    ``t_end`` (optional, traced ok) clips every window at an exclusive
    flat-tile bound: the effective range is ``[t0, min(t0+n_local,
    t_end))`` and a window starting at/after ``t_end`` matches nothing.
    The exchange uses this for strips that do not divide by the "part"
    axis — padded sub-windows must not count the NEXT strip's tiles (or
    anything at all, when fully past the strip) against an edge budget.
    """
    _, _, y0, y1 = _bbox_bounds(mx, my, rad, grid)
    t0 = jnp.asarray(t0, jnp.int32)
    if t_end is None:
        lim = t0 + n_local
        live = None
    else:
        t_end = jnp.asarray(t_end, jnp.int32)
        lim = jnp.minimum(t0 + n_local, t_end)
        live = t0 < t_end
    r0 = t0 // grid.nx
    r1 = (lim - 1) // grid.nx
    if t0.ndim:
        shape = t0.shape + (1,) * y0.ndim
        r0 = r0.reshape(shape)
        r1 = r1.reshape(shape)
        if live is not None:
            live = live.reshape(shape)
    out = valid & (y0 <= r1) & (y1 >= r0)
    return out if live is None else out & live


def grow_tile_budget(budget: int, n_tiles: int, *, growth: float = 2.0,
                     round_to: int = 16) -> int:
    """Geometric growth for a static per-splat tile budget that reported
    overflow — the sorted-assignment mirror of ``TierSchedule.
    note_overflow`` (drivers rebuild the step with the grown budget instead
    of letting truncation persist).  Clamped to [1, n_tiles], where the
    bbox expansion provably cannot drop."""
    b = int(np.ceil(max(int(budget), 1) * growth))
    b = -(-b // round_to) * round_to
    return max(1, min(b, max(int(n_tiles), 1)))


def _expand_splat_tiles(mx, my, rad, valid, grid: TileGrid, *,
                        budget: int, t0=None, n_local: Optional[int] = None):
    """Expand one splat table into per-splat candidate (tile, depth, idx)
    triples over a static ``budget`` of bbox tile slots.

    mx/my/rad/valid (N,); ``t0`` (dynamic scalar, default 0) is the
    flat-tile offset of a LOCAL window of ``n_local`` row-major tiles (the
    distributed strip case; None/None = the full grid).  Returns
    (tile (N, B) int32 LOCAL ids with n_local as miss/pad sentinel,
    overflow () int32 counting bbox candidate slots dropped past the
    budget — conservative: bbox slots, a superset of true circle hits, so
    0 still proves exactness).

    The bbox low edge uses ceil-1 (not floor, see _bbox_bounds) so a circle
    exactly tangent to a tile boundary still enumerates the tile the dense
    sweep's clamp test counts as a hit; the exact circle/rect test then
    decides membership with the same arithmetic as the dense path.
    """
    Tl = grid.n_tiles if n_local is None else n_local
    tw = jnp.float32(grid.tile_w)
    th = jnp.float32(grid.tile_h)
    x0, x1, y0, y1 = _bbox_bounds(mx, my, rad, grid)
    if t0 is not None:
        # clamp the bbox rows to the window's row span (the window is a
        # contiguous row-major tile range, so rows [t0//nx, (t0+Tl-1)//nx]
        # are a superset of its tiles) — budget slots stop paying for
        # strip-foreign rows
        y0 = jnp.maximum(y0, t0 // grid.nx)
        y1 = jnp.minimum(y1, (t0 + Tl - 1) // grid.nx)

    # bw >= 1 for any rad >= 0 (the clamped range is non-empty); the
    # maximum() only guards the integer division against degenerate
    # negative-radius inputs, whose nt is already 0
    bw = jnp.maximum(x1 - x0 + 1, 1)
    nt = jnp.where(valid,
                   jnp.maximum(x1 - x0 + 1, 0)
                   * jnp.maximum(y1 - y0 + 1, 0), 0)
    jj = jnp.arange(budget, dtype=jnp.int32)[None, :]
    inb = jj < nt[:, None]                            # (N, B)
    ty = y0[:, None] + jj // bw[:, None]
    tx = x0[:, None] + jj % bw[:, None]
    # exact circle/rect test — identical arithmetic to the dense sweep
    lox = tx.astype(jnp.float32) * tw
    loy = ty.astype(jnp.float32) * th
    cx = jnp.clip(mx[:, None], lox, lox + tw)
    cy = jnp.clip(my[:, None], loy, loy + th)
    dx = mx[:, None] - cx
    dy = my[:, None] - cy
    hit = inb & (dx * dx + dy * dy <= (rad * rad)[:, None])
    flat = ty * grid.nx + tx
    if t0 is not None:
        flat = flat - t0
        hit &= (flat >= 0) & (flat < Tl)
    tile = jnp.where(hit, flat, Tl).astype(jnp.int32)
    overflow = jnp.maximum(nt - budget, 0).sum().astype(jnp.int32)
    return tile, overflow


def _splat_depth_ranks(depth):
    """Stable (depth asc, splat idx asc) ranking of a (N,) depth table.

    -> (rank_of (N,) int32 rank per ORIGINAL splat, perm (N,) int32
    original index per rank).  Depths are positive, so their float32 bit
    patterns are monotone as unsigned ints; the stable sort realizes the
    splat-index tie-break — together exactly topk_by_score_then_index's
    (score desc, idx asc) order.  Invalid splats may carry arbitrary
    depths; they rank SOMEWHERE, harmlessly, since they emit no candidates.
    """
    N = depth.shape[0]
    iota = jnp.arange(N, dtype=jnp.int32)
    _, perm = lax.sort((lax.bitcast_convert_type(depth, jnp.uint32), iota),
                       num_keys=1)
    rank_of = jnp.zeros((N,), jnp.int32).at[perm].set(iota)
    return rank_of, perm


def _segment_topk_packed(tile, rank_of, perm, depth, *, n_tiles: int,
                         K: int, rank_bits: int):
    """Per-tile first-K of the candidate set via ONE single-operand sort.

    tile (N, B) LOCAL ids (sentinel == ``n_tiles``) from
    _expand_splat_tiles; rank_of/perm/depth from _splat_depth_ranks.  Each
    candidate packs into a single uint32 key ``tile << rank_bits | rank``
    — ascending keys are exactly the (tile, depth, splat idx) lexicographic
    order, and the key alone DECODES back to (tile, splat idx, depth), so
    the sort carries no payload.  XLA's single-operand u32 sort stays on a
    fast vectorized path (~25 ms / 384k on CPU) where the variadic
    multi-key comparator sort is ~10x slower — that difference is the whole
    CPU viability of this path.  Group boundaries come from one
    ``searchsorted`` over the tile prefixes and the (T, K) output is pure
    gathers — no scatter (XLA CPU scatter costs ~55 ns/element).

    Ranks past K fall off (the same depth-ordered truncation as the dense
    top-k); empty slots carry (idx 0, score NEG) — bit-identical to the
    dense sweep.
    """
    N, B = tile.shape
    M = N * B
    hit = tile < n_tiles
    packed = jnp.where(
        hit,
        (tile.astype(jnp.uint32) << rank_bits)
        | rank_of[:, None].astype(jnp.uint32),
        jnp.uint32(0xFFFFFFFF)).reshape(-1)
    skeys = lax.sort(packed)                          # (M,) single-operand
    bounds = jnp.searchsorted(
        skeys, jnp.arange(n_tiles + 1, dtype=jnp.uint32) << rank_bits)
    pos = bounds[:n_tiles, None] + jnp.arange(K, dtype=bounds.dtype)[None, :]
    live = pos < bounds[1:, None]                     # within my tile's run
    key_at = skeys[jnp.minimum(pos, M - 1)]
    r = jnp.minimum((key_at
                     & jnp.uint32((1 << rank_bits) - 1)).astype(jnp.int32),
                    N - 1)
    src = perm[r]                                     # original splat index
    idx = jnp.where(live, src, 0)
    score = jnp.where(live, -depth[src], NEG)
    return idx, score


def _segment_topk_sort3(tile, depth, *, n_tiles: int, K: int):
    """Variadic-sort fallback for _segment_topk_packed when
    ``log2(T+1) + log2(N)`` exceeds the 32 packed key bits: a stable
    three-key lax.sort over (tile, depth, splat idx) — same output, ~10x
    slower on CPU (scalar comparator lowering); huge-N/huge-T callers
    should shard (the distributed strip windows keep both factors small).
    """
    N, B = tile.shape
    M = N * B
    dk = jnp.where(tile < n_tiles,
                   jnp.broadcast_to(depth[:, None], tile.shape),
                   jnp.float32(1e30))
    sidx = jnp.broadcast_to(jnp.arange(N, dtype=jnp.int32)[:, None], (N, B))
    tile_s, _, idx_s = lax.sort(
        (tile.reshape(-1), dk.reshape(-1), sidx.reshape(-1)), num_keys=3)
    pos = jnp.arange(M, dtype=jnp.int32)
    start = jnp.concatenate([jnp.ones((1,), bool), tile_s[1:] != tile_s[:-1]])
    rank = pos - lax.cummax(jnp.where(start, pos, 0), axis=0)
    live = (tile_s < n_tiles) & (rank < K)
    row = jnp.where(live, tile_s, n_tiles)            # scratch row/col
    col = jnp.where(live, rank, K)
    idx = jnp.zeros((n_tiles + 1, K + 1), jnp.int32) \
        .at[row, col].set(jnp.where(live, idx_s, 0))
    score = jnp.full((n_tiles + 1, K + 1), NEG, jnp.float32) \
        .at[row, col].set(jnp.where(live, -depth[idx_s], NEG))
    return idx[:n_tiles, :K], score[:n_tiles, :K]


@scope("assign")
def sorted_assign_window(mx, my, rad, valid, depth, grid: TileGrid, *,
                         K: int, t0=None, n_local: Optional[int] = None,
                         tile_budget: Optional[int] = None):
    """Sort-based assignment of one raw splat table over a LOCAL tile
    window: the building block ``assign_tiles_sorted`` (full grid) and the
    distributed strip-local assignment (core.distributed) share.

    mx/my/rad/valid/depth (N,) splat columns; ``t0`` a (possibly traced)
    flat-tile offset and ``n_local`` the static window length — None/None
    means the full grid.  -> (idx (Tl, K) int32 LOCAL rows, score (Tl, K),
    overflow () int32) with exactly ``assign_tiles``'s slot semantics
    (bit-identical to the dense sweep restricted to the window whenever the
    budget covers every splat's bbox candidate count).
    """
    Tl = grid.n_tiles if n_local is None else int(n_local)
    N = mx.shape[0]
    if N == 0:
        return (jnp.zeros((Tl, K), jnp.int32),
                jnp.full((Tl, K), NEG, jnp.float32),
                jnp.zeros((), jnp.int32))
    if tile_budget is None and not isinstance(mx, jax.core.Tracer):
        # concrete splats (outside jit/vmap): size the budget exactly from
        # this table — provably no drops, the analogue of auto_tier_caps'
        # outside-jit auto-sizing.  Under tracing the static
        # DEFAULT_TILE_BUDGET applies; callers with a hot jitted loop
        # probe a budget host-side instead (render.tile_count_probe_jit).
        x0, x1, y0, y1 = _bbox_bounds(mx, my, rad, grid)
        cnt = jnp.maximum(x1 - x0 + 1, 0) * jnp.maximum(y1 - y0 + 1, 0)
        tile_budget = int(np.asarray(jnp.where(valid, cnt, 0).max()))
    budget = resolve_tile_budget(grid.n_tiles, tile_budget)
    tile, overflow = _expand_splat_tiles(
        mx, my, rad, valid, grid, budget=budget, t0=t0, n_local=Tl)
    rank_of, perm = _splat_depth_ranks(depth)
    rank_bits = max(1, (N - 1).bit_length())
    if Tl.bit_length() + rank_bits <= 32:
        idx, score = _segment_topk_packed(tile, rank_of, perm, depth,
                                          n_tiles=Tl, K=K,
                                          rank_bits=rank_bits)
    else:
        idx, score = _segment_topk_sort3(tile, depth, n_tiles=Tl, K=K)
    return idx, score, overflow


def assign_tiles_sorted(splats: Splats2D, grid: TileGrid, *, K: int = 64,
                        tile_budget: Optional[int] = None,
                        return_overflow: bool = False):
    """Sort-based top-K assignment: same contract as ``assign_tiles``.

    The GPU 3D-GS duplicate-and-sort scatter, TPU/static-shape adapted:
    every projected splat expands into the tiles its circle overlaps
    (static per-splat ``tile_budget`` bbox slots; ``None`` sizes it
    EXACTLY from the concrete table outside tracing, and falls back to
    min(T, DEFAULT_TILE_BUDGET) under jit — hot jitted loops probe a
    budget host-side via ``splat_tile_counts`` + ``auto_tile_budget``,
    which is what render_views / fit_partition(s) do), one global stable
    sort by
    (tile, depth, splat idx) groups and orders the candidates, and a
    segmented scatter writes each tile's first K into the (T, K)
    idx/score layout — O(N * B log(N * B)) work, independent of the tile
    count, vs the dense sweep's O(T * N).  The three-key order reproduces
    ``topk_by_score_then_index``'s (score desc, index asc) tie-break, so
    the output — indices, scores, empty slots (idx 0 / score NEG) — is
    BIT-IDENTICAL to the dense sweep whenever the budget covers every
    splat's bbox tile count (``benchmarks/bench_assign.py`` measures the
    crossover; tests/test_tiling_properties.py pins the parity).

    With ``return_overflow=True`` a third () int32 counts bbox candidate
    slots dropped past the budget (the same "0 means provably exact"
    telemetry contract as the coarse pre-cull's counter; conservative —
    dropped slots may not have been true hits).  On overflow a splat keeps
    its budget-first bbox tiles in row-major order, so the loss is
    arbitrary w.r.t. visibility: size budgets to the scene and monitor the
    counter in production.
    """
    idx, score, overflow = sorted_assign_window(
        splats.mean2d[..., 0], splats.mean2d[..., 1], splats.radius,
        splats.valid, splats.depth, grid, K=K, tile_budget=tile_budget)
    return (idx, score, overflow) if return_overflow else (idx, score)


# ---------------------------------------------------------------------------
# Variable-K occupancy binning (tiered rasterization)
# ---------------------------------------------------------------------------


class TierPlan(NamedTuple):
    """Static-shape dispatch schedule for tiered rasterization.

    tile_ids  per tier i: (cap_i,) int32 flat tile ids compacted to the
              front; slots past ``counts[i]`` hold M (one-past-the-end
              sentinel, M = the flat tile count) so scatters with
              ``mode="drop"`` ignore them.  cap_i is STATIC — it is part of
              the traced shape, so a jit cache keyed on the caps never
              recompiles for scenes with the same cap signature.
    counts    (n_tiers,) int32: tiles actually placed per tier (<= cap_i).
    overflow  () int32: tiles that fit no tier because every cap from their
              desired tier upward was full — those tiles are DROPPED from
              rasterization (they render as background).  0 whenever caps
              cover the true tier histogram (auto_tier_caps guarantees it).
    """
    tile_ids: Tuple[jax.Array, ...]
    counts: jax.Array
    overflow: jax.Array


def tile_occupancy(score):
    """(..., T, K) assignment scores -> (..., T) int32 live-entry counts.

    Occupancy is exact when the assignment K covered the true per-tile
    overlap depth; tiles saturating all K slots may be undercounted, which
    is why tiered callers assign at Kmax = the largest tier first.
    """
    return (score > NEG / 2).sum(axis=-1).astype(jnp.int32)


def tile_tiers(occupancy, k_tiers: Sequence[int]):
    """Per-tile tier index: the smallest tier whose K covers the occupancy.

    occupancy (..., T) int32 -> (..., T) int32 in [-1, n_tiers).  Empty
    tiles (occupancy 0) get tier -1 — "no rasterization work at all" (their
    output is exactly zero under the kernel semantics: every slot carries
    alpha 0, so color 0 / coverage 0).  Tiles whose occupancy exceeds even
    the top tier land in the top tier (truncation, same as the dense path
    at K = k_tiers[-1]).
    """
    kt = jnp.asarray(tuple(k_tiers), jnp.int32)
    covered = occupancy[..., None] <= kt               # (..., T, n_tiers)
    tier = jnp.argmax(covered, axis=-1).astype(jnp.int32)
    tier = jnp.where(covered.any(-1), tier, len(tuple(k_tiers)) - 1)
    return jnp.where(occupancy > 0, tier, -1)


def bin_tiles_by_occupancy(occupancy, k_tiers: Sequence[int],
                           tier_caps: Sequence[int]) -> TierPlan:
    """Bin flat tiles into K-tiers with STATIC per-tier capacities.

    occupancy (M,) int32; k_tiers strictly increasing per-tile K budgets;
    tier_caps same length, static ints.  Tiles fill their desired tier
    (smallest K covering their occupancy) in flat-tile-id order; a tile
    whose tier is full PROMOTES to the next larger tier (a bigger K is
    still exact), and tiles that fall off the top are counted in
    ``overflow`` and dropped.  Empty tiles (occupancy 0) are placed in no
    tier — the rasterizer's output for them is identically zero, so the
    scatter's zero-initialised image already IS their result.

    Fully jit-compatible: every output shape depends only on ``tier_caps``.
    """
    k_tiers = tuple(int(k) for k in k_tiers)
    tier_caps = tuple(int(c) for c in tier_caps)
    if len(tier_caps) != len(k_tiers):
        raise ValueError(f"{len(k_tiers)} tiers but {len(tier_caps)} caps")
    if any(b <= a for a, b in zip(k_tiers, k_tiers[1:])):
        raise ValueError(f"k_tiers must be strictly increasing: {k_tiers}")
    M = occupancy.shape[0]
    tier = tile_tiers(occupancy, k_tiers)
    ids = jnp.arange(M, dtype=jnp.int32)
    tile_ids, counts = [], []
    carry = jnp.zeros((M,), bool)           # overflow promoted from below
    for i, cap in enumerate(tier_caps):
        want = (tier == i) | carry
        rank = jnp.cumsum(want) - 1         # id-order position within tier
        take = want & (rank < cap)
        pos = jnp.where(take, jnp.minimum(rank, cap), cap)  # cap = scratch
        buf = jnp.full((cap + 1,), M, jnp.int32)
        buf = buf.at[pos].set(jnp.where(take, ids, M))
        tile_ids.append(buf[:cap])
        counts.append(jnp.minimum(want.sum(), cap).astype(jnp.int32))
        carry = want & ~take
    return TierPlan(tile_ids=tuple(tile_ids),
                    counts=jnp.stack(counts),
                    overflow=carry.sum().astype(jnp.int32))


#: shared "you called a host-side cap sizer under jit" guidance — tier caps
#: are STATIC shapes, so they can only be chosen from concrete telemetry
_TRACED_PROBE_MSG = (
    "{what} was called with traced (abstract) telemetry — it is running "
    "inside jit/vmap/grad/shard_map tracing.  Tier caps are STATIC kernel "
    "shapes, so they must be sized from CONCRETE host-side values.  Move "
    "the probe outside the traced computation: e.g. "
    "occ = occupancy_probe_jit(grid, sched.kmax)(g, cams); sched.probe(occ) "
    "on a single device, or reduce telemetry across a mesh with "
    "core.distributed.make_gs_probe / probe_gs_schedule and feed the "
    "fetched (counts, max_occ) to TierSchedule.probe_counts.  Under jit, "
    "pass the schedule's already-static (k_tiers, tier_caps) instead.")


def _reject_tracers(what: str, *vals):
    if any(isinstance(v, jax.core.Tracer) for v in vals):
        raise TypeError(_TRACED_PROBE_MSG.format(what=what))


def _tier_counts(occupancy, k_tiers: Sequence[int]):
    """Concrete (..., T) occupancy -> (per-tier worst-slice counts, max occ).

    counts[i] = max over leading batch slices of the number of tiles whose
    DESIRED tier (smallest covering K) is i — exactly what
    bin_tiles_by_occupancy fills before promotion, hence what caps must
    cover.  This is the host half of the cross-host telemetry contract:
    core.distributed.make_gs_probe computes the same counts per device and
    pmax-reduces them over the mesh.
    """
    occ = np.asarray(occupancy)
    if occ.size == 0:
        return [0] * len(tuple(k_tiers)), 0
    occ = occ.reshape(-1, occ.shape[-1])
    tiers = np.asarray(tile_tiers(jnp.asarray(occ), k_tiers))
    counts = [int((tiers == i).sum(axis=-1).max())
              for i in range(len(tuple(k_tiers)))]
    return counts, int(occ.max())


def caps_from_tier_counts(counts: Sequence[int], *, slack: float = 1.0,
                          round_to: int = 8, limit: int) -> Tuple[int, ...]:
    """Per-tier tile counts -> static caps: scale by ``slack``, round up to
    ``round_to`` (so nearby probes hash to the same jit cache entry), clamp
    at ``limit`` (the flat tile count of the binning domain, where binning
    provably cannot overflow).  Zero counts keep cap 0 — a zero-cost launch
    that keeps overflow telemetry live if occupancy later grows."""
    caps = []
    for c in counts:
        c = int(c)
        if c:
            c = int(np.ceil(c * slack))
            c = min(-(-c // round_to) * round_to, int(limit))
        caps.append(c)
    return tuple(caps)


def auto_tier_caps(occupancy, k_tiers: Sequence[int], *, slack: float = 1.0,
                   round_to: int = 8) -> Tuple[int, ...]:
    """Host-side cap sizing from CONCRETE occupancy counts.

    occupancy (..., T) (any leading batch axes, e.g. a view axis) ->
    static per-tier caps covering the worst slice of the batch, scaled by
    ``slack`` and rounded up to a multiple of ``round_to`` so nearby scenes
    hash to the same jit cache entry.  Raises under tracing — pass explicit
    ``tier_caps`` inside jit (see the error text for the full recipe).
    """
    _reject_tracers("auto_tier_caps", occupancy)
    occ = np.asarray(occupancy)
    counts, _ = _tier_counts(occ, k_tiers)
    return caps_from_tier_counts(counts, slack=slack, round_to=round_to,
                                 limit=occ.shape[-1] if occ.size else 0)


class TierSchedule:
    """Telemetry-driven (k_tiers, tier_caps) picker for tiered-by-default
    training.

    The tiered rasterizer needs two STATIC inputs — a K ladder and per-tier
    tile capacities — but occupancy is a moving target during training
    (densify adds splats, prune removes them).  TierSchedule closes that
    loop from the telemetry the pipeline already surfaces
    (``tile_occupancy`` of an assignment sweep; ``RenderOut.overflow`` /
    the distributed forward's overflow counter):

      probe(occupancy)   feed CONCRETE per-tile occupancy measured at the
          ladder's Kmax (``render.view_occupancy`` is the standard probe);
          caps are re-sized via ``auto_tier_caps``.  Unoccupied upper
          tiers get cap 0 — a zero-cost launch — which is what keeps the
          telemetry honest: if occupancy later grows into them, their
          tiles overflow LOUDLY (note_overflow grows the caps) instead of
          being silently truncated.  Host-side only — raises under
          tracing, exactly like auto_tier_caps.  ``trim=True`` opts into
          additionally trimming the ladder to the occupied prefix (sparse
          phases stop paying large-K assignment) — but a trimmed Kmax also
          CAPS the occupancy the training step can measure, so growth past
          it is invisible between probes; only enable it for runs that
          re-probe on a schedule (e.g. every densify event), never with a
          single init-time probe.
      train              pass ``(schedule.k_tiers, schedule.tier_caps)`` to
          the step factory; jit caches key on them, so the step recompiles
          only when the schedule actually changes (caps are rounded so
          nearby probes hash identically).
      note_overflow(ov, n_tiles)   a step that reports dropped tiles calls
          this: caps grow geometrically (clamped at ``n_tiles``, where
          binning provably cannot drop).  Returns True when caps changed —
          the signal to rebuild the step.
      densify / prune    occupancy shifted: probe again.

    The full lifecycle (probe -> train -> densify -> re-probe) is
    documented in docs/distributed-training.md.  The coarse pre-cull's
    budget counter (``assign_tiles(return_overflow=True)``) is a separate
    knob: it guards candidate lists, not tier capacities.
    """

    def __init__(self, k_tiers: Sequence[int] = (8, 32, 128), *,
                 slack: float = 1.25, round_to: int = 8,
                 growth: float = 2.0, trim: bool = False):
        ladder = tuple(int(k) for k in k_tiers)
        if not ladder or any(b <= a for a, b in zip(ladder, ladder[1:])):
            raise ValueError("k_tiers must be a non-empty strictly "
                             f"increasing ladder: {ladder}")
        self.ladder = ladder             # full ladder (probe depth = max)
        self.slack = float(slack)
        self.round_to = int(round_to)
        self.growth = float(growth)
        self.trim = bool(trim)           # see class docstring before enabling
        self.k_tiers: Tuple[int, ...] = ladder   # active tiers
        self.tier_caps: Optional[Tuple[int, ...]] = None  # None until probe

    @property
    def kmax(self) -> int:
        """Assignment depth probes must use (occupancy is a lower bound for
        tiles that saturate it, so probing shallower would under-cap)."""
        return self.ladder[-1]

    def probe(self, occupancy):
        """Re-pick (k_tiers, tier_caps) from concrete (..., T) occupancy.

        Returns the new ``(k_tiers, tier_caps)``.  Call after every
        densify/prune event — and at init — with occupancy measured at
        ``self.kmax``.  Raises with a how-to-fix recipe when called under
        JAX tracing (caps are static shapes; see ``probe_counts`` for the
        distributed/multi-host entry point).
        """
        _reject_tracers("TierSchedule.probe", occupancy)
        occ = np.asarray(occupancy)
        counts, max_occ = _tier_counts(occ, self.ladder)
        return self.probe_counts(counts, max_occ,
                                 n_tiles=occ.shape[-1] if occ.size else 0)

    def probe_counts(self, tier_counts, max_occ, *, n_tiles: int):
        """Re-pick (k_tiers, tier_caps) from REDUCED telemetry: per-tier
        worst-domain tile counts (over the FULL ladder) plus the max
        occupancy, with ``n_tiles`` the flat tile count of one binning
        domain (the cap clamp, where binning provably cannot drop).

        This is the cross-host probe entry point: every device of a mesh
        computes (counts, max_occ) over its own folded (Vl*T,) strip and a
        pmax reduction (core.distributed.make_gs_probe) makes the result
        identical on every host — so each host independently lands on the
        SAME cap ladder and compiles the identical program.  ``probe``
        delegates here after counting host-side.
        """
        _reject_tracers("TierSchedule.probe_counts", tier_counts, max_occ)
        counts = [int(c) for c in np.asarray(tier_counts).reshape(-1)]
        if len(counts) != len(self.ladder):
            raise ValueError(
                f"probe_counts got {len(counts)} tier counts for the "
                f"{len(self.ladder)}-tier ladder {self.ladder}; counts must "
                "be measured over the schedule's FULL ladder")
        max_occ = int(max_occ)
        # default: keep the FULL ladder — unoccupied upper tiers cost
        # nothing (cap 0 -> no launch) and keep overflow telemetry live.
        # trim=True: smallest ladder prefix covering max occupancy; a probe
        # that saturated Kmax keeps the full ladder (true occupancy may be
        # deeper than we could measure).  Counts are tier-for-tier valid on
        # the trimmed prefix: trimming only happens when max_occ fits it,
        # so the dropped upper tiers were empty.
        active = self.ladder
        if self.trim:
            for i, k in enumerate(self.ladder):
                if max_occ <= k and k < self.ladder[-1]:
                    active = self.ladder[: i + 1]
                    break
        self.k_tiers = active
        self.tier_caps = caps_from_tier_counts(
            counts[: len(active)], slack=self.slack, round_to=self.round_to,
            limit=n_tiles)
        return self.k_tiers, self.tier_caps

    def note_overflow(self, overflow, n_tiles: int) -> bool:
        """React to a step's dropped-tile counter: grow every cap by
        ``growth`` (clamped at ``n_tiles``, the flat tile count of the
        binning domain, where overflow is impossible).  Returns True when
        the caps changed — rebuild the step before the next iteration.
        No-op (False) when the counter is 0 or no probe has run yet."""
        ov = int(np.asarray(overflow).sum())
        if ov <= 0 or self.tier_caps is None:
            return False
        grown = tuple(
            min(int(n_tiles), max(self.round_to,
                                  int(np.ceil(c * self.growth))))
            for c in self.tier_caps)
        if grown == self.tier_caps:
            return False
        self.tier_caps = grown
        return True

    # -- (de)serialization: checkpoint the schedule alongside params so a
    # resumed run keeps its probed caps instead of re-probing from scratch

    def state_dict(self) -> dict:
        """JSON-able snapshot of the full schedule state (ladder, knobs,
        active tiers, caps).  Stored in CheckpointManager ``extra`` by
        ``fit_partition`` / ``core.distributed.fit_partitions``."""
        return {
            "ladder": list(self.ladder),
            "slack": self.slack,
            "round_to": self.round_to,
            "growth": self.growth,
            "trim": self.trim,
            "k_tiers": list(self.k_tiers),
            "tier_caps": None if self.tier_caps is None
            else list(self.tier_caps),
        }

    def load_state(self, state: dict) -> "TierSchedule":
        """Restore a ``state_dict`` snapshot IN PLACE (the checkpoint wins
        over constructor arguments) and return self."""
        ladder = tuple(int(k) for k in state["ladder"])
        if not ladder or any(b <= a for a, b in zip(ladder, ladder[1:])):
            raise ValueError(f"checkpointed ladder is invalid: {ladder}")
        self.ladder = ladder
        self.slack = float(state["slack"])
        self.round_to = int(state["round_to"])
        self.growth = float(state["growth"])
        self.trim = bool(state["trim"])
        self.k_tiers = tuple(int(k) for k in state["k_tiers"])
        caps = state["tier_caps"]
        self.tier_caps = None if caps is None else tuple(int(c) for c in caps)
        return self

    @classmethod
    def from_state(cls, state: dict) -> "TierSchedule":
        """Rebuild a schedule from a ``state_dict`` snapshot."""
        return cls(state["ladder"]).load_state(state)

    def __repr__(self):
        return (f"TierSchedule(k_tiers={self.k_tiers}, "
                f"tier_caps={self.tier_caps}, ladder={self.ladder})")


def splat_features(splats: Splats2D):
    """Per-splat kernel features: (N, FEAT_DIM) rows [mx, my, conicA, conicB,
    conicC, r, g, b, alpha, 0-pad]; invalid splats get alpha=0.
    Batch-polymorphic over leading dims ((..., N, FEAT_DIM) in general —
    the distributed path carries (P, N), render_batch (V, N))."""
    a, b, c = splats.cov2d[..., 0], splats.cov2d[..., 1], splats.cov2d[..., 2]
    det = jnp.maximum(a * c - b * b, 1e-12)
    conic = jnp.stack([c / det, -b / det, a / det], -1)      # (..., 3)
    alpha = jnp.where(splats.valid, splats.alpha, 0.0)
    feat = jnp.concatenate(
        [splats.mean2d, conic, splats.rgb, alpha[..., None]], axis=-1
    )                                                        # (..., 9)
    pad = FEAT_DIM - feat.shape[-1]
    return jnp.pad(feat, ((0, 0),) * (feat.ndim - 1) + ((0, pad),))


@scope("gather")
def gather_features_at(feat, idx, score):
    """Gather rows of a (N, FEAT_DIM) feature table into per-tile lists.

    feat (N, F); idx (..., K) int32 rows; score (..., K) with NEG marking
    empty slots -> (..., K, F).  Empty slots get alpha=0 -> contribute
    nothing.  This gather is plain jnp (differentiable); its transpose
    (scatter-add) is what routes the kernel's per-tile grads back to
    gaussians.  The tiered path calls this once per K-tier with that tier's
    compacted (cap_i, K_i) index table.
    """
    tile_feat = feat[idx]                                    # (..., K, F)
    live = score > NEG / 2                                   # (..., K)
    alpha = jnp.where(live, tile_feat[..., 8], 0.0)
    return jnp.concatenate(
        [tile_feat[..., :8], alpha[..., None], tile_feat[..., 9:]], axis=-1
    )


@scope("gather")
def gather_tile_features(splats: Splats2D, idx, score):
    """Pack per-tile splat features: (T, K, FEAT_DIM).

    splats with (N,) leading axis; idx/score (T, K) from assign_tiles.
    See gather_features_at for the slot semantics.
    """
    return gather_features_at(splat_features(splats), idx, score)


def untile_image(tiles, grid: TileGrid):
    """(T, 4, th, tw) kernel output -> (H, W, 4) image (cropped to grid size)."""
    th, tw = grid.tile_h, grid.tile_w
    img = tiles.reshape(grid.ny, grid.nx, 4, th, tw)
    img = img.transpose(0, 3, 1, 4, 2).reshape(grid.ny * th, grid.nx * tw, 4)
    return img[: grid.height, : grid.width]


def tile_image(img, grid: TileGrid):
    """(H, W, C) image -> (T, C, th, tw) tile layout (inverse of
    untile_image; pixels past the image edge — the grid's padding rows /
    columns — are zero-filled).  This is how host images become the
    ``gt_tiles`` batches the distributed step consumes; masks tile the same
    way via a singleton channel."""
    th, tw = grid.tile_h, grid.tile_w
    Hp, Wp = grid.ny * th, grid.nx * tw
    img = jnp.pad(img, ((0, Hp - img.shape[0]), (0, Wp - img.shape[1]),
                        (0, 0)))
    t = img.reshape(grid.ny, th, grid.nx, tw, img.shape[-1])
    return t.transpose(0, 2, 4, 1, 3).reshape(
        grid.n_tiles, img.shape[-1], th, tw)


# ---------------------------------------------------------------------------
# Serving-cache helpers: pose-bucket keys + assignment-table reuse
# ---------------------------------------------------------------------------

#: default pose-quantization resolution for the serving assignment cache:
#: bucket edge = 1/POSE_BINS in view-matrix / normalized-focal units, i.e.
#: sub-millimeter pose snapping on a unit-scale scene — fine enough that
#: snapped renders are visually identical, coarse enough that a camera
#: jittering around a viewpoint keeps hitting one bucket.
POSE_BINS = 1024.0


def quantize_pose(view, fx, fy, *, bins: float = POSE_BINS):
    """Quantize one camera pose onto a lattice of bucket edge ``1/bins``.

    -> ``(key, (view', fx', fy'))`` where ``key`` is a hashable tuple of
    int bucket coordinates (the 16 view-matrix entries + the two focals,
    focals scaled into the same lattice by 1/1024 so pixel-unit focal
    lengths quantize at a comparable relative resolution) and the primed
    triple is the CANONICAL pose — the dequantized lattice point, float32.

    The serving cache renders the canonical pose, not the requested one:
    any two cameras inside one bucket therefore produce *bit-identical*
    renders, and a cache HIT is bit-identical to the cold MISS that
    populated the entry by construction (the (T, K) table was extracted
    from the exact pose being rendered).  ``bins`` is the fidelity /
    hit-rate knob — snapping error is <= 1/(2*bins) per matrix entry.
    Entry-wise rounding leaves the rotation block orthonormal only to
    O(1/bins); projection never re-orthonormalizes, so this is pure pose
    noise, not a correctness hazard.
    """
    v = np.asarray(view, np.float64).reshape(4, 4)
    qv = np.rint(v * bins)
    qf = np.rint(np.asarray([fx, fy], np.float64) * (bins / 1024.0))
    key = tuple(int(x) for x in qv.ravel()) + tuple(int(x) for x in qf)
    canon_view = (qv / bins).astype(np.float32)
    canon_f = (qf * (1024.0 / bins)).astype(np.float32)
    return key, (canon_view, canon_f[0], canon_f[1])


def slice_table(idx, score, k: int):
    """Depth-``k`` prefix of a cached ``(..., K)`` assignment table.

    ``assign_tiles`` emits every tile's list in the total order
    (score desc, index asc), so the first ``k`` columns of a depth-K table
    ARE the depth-``k`` assignment, bit for bit — one cached Kmax table
    serves every ladder rung k <= Kmax without re-running assignment
    (``tests/test_serving.py::test_slice_table_prefix_property`` pins
    this against a direct K=k assignment).
    """
    if k > idx.shape[-1]:
        raise ValueError(
            f"slice_table: k={k} exceeds cached table depth {idx.shape[-1]}")
    return idx[..., :k], score[..., :k]

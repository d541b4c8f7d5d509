"""Property-style tests for tile assignment (core/tiling.py).

Pins the contract the rasterizer relies on:
  * with K >= the true per-tile overlap depth, assign_tiles is EXACT — it
    matches a brute-force per-tile circle/rect test + depth sort;
  * live entries come out front-to-back (scores non-increasing = depth
    non-decreasing);
  * the dense sweep's gather-free merge equals the gathering
    ``topk_by_score_then_index`` bit for bit, and compiles to no gather;
  * the coarse superblock pre-cull returns identical (idx, score) to the
    dense path on live slots whenever its candidate budget covers the true
    per-superblock occupancy (empty-slot idx values are unspecified);
  * the sort-based path (assign_tiles_sorted) is BIT-IDENTICAL to the
    dense sweep — indices, scores, empty slots, overflow counters —
    whenever its per-splat tile budget covers the scene, including
    duplicate scores, saturated K, empty tiles and under vmap; a starved
    budget fires the overflow counter with the exact dropped-slot count.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.projection import Splats2D
from repro.core.tiling import (NEG, SORTED_MIN_TILES, TileGrid,
                               _merge_block_topk, assign_tiles,
                               assign_tiles_sorted, resolve_assign_impl,
                               tile_bounds, topk_by_score_then_index)


def random_splats(seed, n, w, h, *, rmax=9.0, invalid_frac=0.1):
    r = np.random.default_rng(seed)
    return Splats2D(
        mean2d=jnp.asarray(r.uniform([-12, -12], [w + 12, h + 12], (n, 2)),
                           jnp.float32),
        cov2d=jnp.ones((n, 3), jnp.float32),
        depth=jnp.asarray(r.uniform(0.1, 10.0, n), jnp.float32),
        rgb=jnp.asarray(r.uniform(0, 1, (n, 3)), jnp.float32),
        alpha=jnp.asarray(r.uniform(0.1, 0.9, n), jnp.float32),
        radius=jnp.asarray(r.uniform(0.5, rmax, n), jnp.float32),
        valid=jnp.asarray(r.uniform(size=n) > invalid_frac),
    )


def brute_force(splats, grid, K):
    """O(T*N) numpy oracle: exact overlap set per tile, depth-sorted, top-K."""
    lo, hi = (np.asarray(x) for x in tile_bounds(grid))
    mean = np.asarray(splats.mean2d)
    rad = np.asarray(splats.radius)
    depth = np.asarray(splats.depth)
    valid = np.asarray(splats.valid)
    out = []
    for t in range(grid.n_tiles):
        cx = np.clip(mean[:, 0], lo[t, 0], hi[t, 0])
        cy = np.clip(mean[:, 1], lo[t, 1], hi[t, 1])
        hit = ((mean[:, 0] - cx) ** 2 + (mean[:, 1] - cy) ** 2
               <= rad ** 2) & valid
        ids = np.nonzero(hit)[0]
        # front-to-back; ties broken by index (matches stable top_k on -depth)
        ids = ids[np.argsort(depth[ids], kind="stable")]
        out.append(ids[:K])
    return out


@pytest.mark.parametrize("seed,n,res,K", [
    (0, 150, 32, 64),
    (1, 300, 48, 96),
    (2, 60, 64, 64),
])
def test_assign_tiles_matches_brute_force_when_k_sufficient(seed, n, res, K):
    grid = TileGrid(res, res, 8, 16)
    splats = random_splats(seed, n, res, res)
    idx, score = assign_tiles(splats, grid, K=K)
    idx, score = np.asarray(idx), np.asarray(score)
    depth = np.asarray(splats.depth)
    want = brute_force(splats, grid, K)
    # K must really cover the worst tile for this to be an exactness test
    assert max(len(w) for w in want) <= K
    for t in range(grid.n_tiles):
        live = score[t] > NEG / 2
        got = idx[t][live]
        assert len(got) == len(want[t])
        # same SET of splats; order may differ only within equal depths
        np.testing.assert_array_equal(np.sort(got), np.sort(want[t]))
        np.testing.assert_allclose(depth[got], depth[want[t]])


@pytest.mark.parametrize("seed", [3, 4])
def test_assign_tiles_front_to_back(seed):
    grid = TileGrid(64, 64, 8, 16)
    splats = random_splats(seed, 400, 64, 64)
    idx, score = assign_tiles(splats, grid, K=32)
    score = np.asarray(score)
    # scores (=-depth) non-increasing along K: front-to-back compositing order
    assert (np.diff(score, axis=1) <= 1e-6).all()
    depth = np.asarray(splats.depth)[np.asarray(idx)]
    live = score > NEG / 2
    d = np.where(live, depth, 1e30)   # finite sentinel: diff stays NaN-free
    assert (np.diff(d, axis=1) >= -1e-6).all()


@pytest.mark.parametrize("seed,n,res,sb", [
    (5, 200, 64, 2),
    (6, 500, 64, 2),
    (7, 350, 128, 4),
])
def test_coarse_cull_matches_dense(seed, n, res, sb):
    grid = TileGrid(res, res, 8, 16)
    splats = random_splats(seed, n, res, res, rmax=6.0)
    i0, s0 = assign_tiles(splats, grid, K=24)
    # full budget: provably no overflow -> exact (and the counter agrees)
    i1, s1, ov1 = assign_tiles(splats, grid, K=24, coarse=sb,
                               coarse_budget=n, return_overflow=True)
    assert int(ov1) == 0
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))
    live = np.asarray(s0) > NEG / 2
    np.testing.assert_array_equal(np.asarray(i0)[live], np.asarray(i1)[live])
    # auto budget on these scenes also covers the occupancy
    i2, s2, ov2 = assign_tiles(splats, grid, K=24, coarse=sb,
                               return_overflow=True)
    assert int(ov2) == 0
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s2))
    np.testing.assert_array_equal(np.asarray(i0)[live], np.asarray(i2)[live])


def test_coarse_overflow_counter_fires_on_saturated_budget():
    """A starved budget must be SURFACED, not silently wrong: the counter
    reports exactly the dropped (superblock, splat) candidate pairs."""
    grid = TileGrid(64, 64, 8, 16)
    splats = random_splats(8, 400, 64, 64, rmax=6.0, invalid_frac=0.0)
    from repro.core.tiling import coarse_candidates
    cand_full, ov_full = coarse_candidates(
        splats.mean2d, splats.radius, splats.valid, grid, sb=2, budget=400)
    assert int(ov_full) == 0
    occ = (np.asarray(cand_full) < 400).sum(axis=1)       # true occupancy
    budget = max(int(occ.max()) // 2, 1)
    _, ov = coarse_candidates(
        splats.mean2d, splats.radius, splats.valid, grid, sb=2,
        budget=budget)
    want = np.maximum(occ - budget, 0).sum()
    assert int(ov) == want and want > 0
    # the dense path never drops -> overflow is identically 0
    _, _, ov_dense = assign_tiles(splats, grid, K=24, return_overflow=True)
    assert int(ov_dense) == 0


def test_topk_tiebreak_is_merge_order_invariant():
    """Duplicate depths at the K boundary: the secondary splat-index key
    must make assignment independent of the block/merge order (the ROADMAP
    tie-break divergence item).  With many equal-depth splats per tile and
    K smaller than the overlap, different block sizes change the merge
    order — idx must not change."""
    res = 32
    grid = TileGrid(res, res, 8, 16)
    r = np.random.default_rng(42)
    n = 300
    depths = np.repeat(r.uniform(0.5, 5.0, n // 4), 4)[:n]  # 4-way ties
    splats = random_splats(9, n, res, res, rmax=12.0, invalid_frac=0.0)
    splats = splats._replace(depth=jnp.asarray(depths, jnp.float32))
    idx_ref, score_ref = assign_tiles(splats, grid, K=8, block=n)
    for block in (7, 32, 128):
        idx_b, score_b = assign_tiles(splats, grid, K=8, block=block)
        np.testing.assert_array_equal(np.asarray(idx_ref), np.asarray(idx_b))
        np.testing.assert_array_equal(np.asarray(score_ref),
                                      np.asarray(score_b))
    # and the coarse path agrees bit-for-bit on live slots too
    idx_c, score_c = assign_tiles(splats, grid, K=8, coarse=2,
                                  coarse_budget=n)
    live = np.asarray(score_ref) > NEG / 2
    np.testing.assert_array_equal(np.asarray(score_ref), np.asarray(score_c))
    np.testing.assert_array_equal(np.asarray(idx_ref)[live],
                                  np.asarray(idx_c)[live])
    # within equal scores the indices come out ascending (front-to-back
    # order with a deterministic tie order)
    sc, ix = np.asarray(score_ref), np.asarray(idx_ref)
    same = (np.diff(sc, axis=1) == 0) & (sc[:, :-1] > NEG / 2)
    assert (np.diff(ix, axis=1)[same] > 0).all()


@pytest.mark.parametrize("lead,n,block,K,levels,hit", [
    ((16,), 300, 64, 8, 5, 0.6),        # 1-D rows; ties straddle slot K
    ((4, 2, 8), 200, 64, 8, 3, 0.6),    # (V, Pl, T) rows, as vmapped
    ((8,), 100, 32, 8, 4, 0.0),         # every carry and block empty
    ((8,), 150, 64, 8, 2, 0.9),         # last block padded (150 = 2x64+22)
    ((8,), 5, 8, 8, 2, 0.8),            # N < K: block == K, padded
])
def test_merge_block_topk_matches_gather_merge(lead, n, block, K, levels,
                                               hit):
    """The dense sweep's merge decodes its winners' splat indices instead of
    gathering them from the merged (K + block)-wide row: at every block of
    a sweep it must equal ``topk_by_score_then_index`` on the same
    concatenation, bit for bit, empty slots included."""
    r = np.random.default_rng(len(lead) * 1000 + n)
    nb = -(-n // block)
    sc = np.where(r.uniform(size=lead + (n,)) < hit,
                  -r.integers(1, levels + 1, lead + (n,)), NEG)
    sc = np.pad(sc, [(0, 0)] * len(lead) + [(0, nb * block - n)],
                constant_values=NEG).astype(np.float32)
    merge = jax.jit(_merge_block_topk, static_argnums=4)
    gather = jax.jit(topk_by_score_then_index, static_argnums=2)
    top_s = jnp.full(lead + (K,), NEG, jnp.float32)
    top_i = jnp.zeros(lead + (K,), jnp.int32)
    for b in range(nb):
        b0 = b * block
        score = jnp.asarray(sc[..., b0:b0 + block])
        cat_i = jnp.broadcast_to(b0 + jnp.arange(block, dtype=jnp.int32),
                                 score.shape)
        want_s, want_i = gather(jnp.concatenate([top_s, score], -1),
                                jnp.concatenate([top_i, cat_i], -1), K)
        top_s, top_i = merge(top_s, top_i, score, jnp.int32(b0), K)
        np.testing.assert_array_equal(np.asarray(top_i), np.asarray(want_i))
        np.testing.assert_array_equal(np.asarray(top_s).view(np.int32),
                                      np.asarray(want_s).view(np.int32))
    top_s = np.asarray(top_s)
    if hit == 0.0:
        assert (top_s == NEG).all() and not np.asarray(top_i).any()
    elif n * hit > 2 * K:
        # ties really straddle the boundary: some row's live slot-K-1 score
        # is also held by one of that row's losers
        kth = top_s[..., -1:]
        straddle = (sc == kth).sum(-1) > (top_s == kth).sum(-1)
        assert (straddle & (kth[..., 0] > NEG / 2)).any()


@pytest.mark.parametrize("path", ["assign_tiles", "assign_tiles_local"])
def test_dense_sweep_compiles_without_gather(path):
    """The dense sweep has no gather, neither as a traced primitive (whose
    op name the chip trace shows under ``gs.assign``) nor in the compiled
    program: block winners' indices are arithmetic and carried ones a
    compare-and-select (the coarse path, whose candidates are data, keeps
    its gather)."""
    from repro.core.distributed import _assign_tiles_local

    grid = TileGrid(64, 48, 8, 16)
    splats = random_splats(5, 700, 64, 48)
    if path == "assign_tiles":
        fn = jax.jit(lambda s: assign_tiles(s, grid, K=16, block=256,
                                            impl="dense"))
        args = (splats,)
    else:
        lo, hi = tile_bounds(grid)
        fn = jax.jit(lambda m, r, d, v: _assign_tiles_local(
            m, r, d, v, lo, hi, K=16, block=256, impl="dense"))
        args = tuple(jnp.stack([x, x]) for x in (
            splats.mean2d, splats.radius, splats.depth, splats.valid))
    assert "gather" not in str(jax.make_jaxpr(fn)(*args))
    assert "gather(" not in fn.lower(*args).compile().as_text()


# ---------------------------------------------------------------------------
# Sort-based assignment (assign_tiles_sorted) vs the dense oracle
# ---------------------------------------------------------------------------


def _bbox_tile_counts(splats, grid):
    """Numpy oracle of the sorted path's per-splat bbox candidate count
    (the quantity its budget bounds and its overflow counter reports)."""
    mean = np.asarray(splats.mean2d)
    rad = np.asarray(splats.radius)
    valid = np.asarray(splats.valid)
    x0 = np.clip(np.ceil((mean[:, 0] - rad) / grid.tile_w) - 1,
                 0, grid.nx - 1)
    x1 = np.clip(np.floor((mean[:, 0] + rad) / grid.tile_w), 0, grid.nx - 1)
    y0 = np.clip(np.ceil((mean[:, 1] - rad) / grid.tile_h) - 1,
                 0, grid.ny - 1)
    y1 = np.clip(np.floor((mean[:, 1] + rad) / grid.tile_h), 0, grid.ny - 1)
    return np.where(valid, (x1 - x0 + 1) * (y1 - y0 + 1), 0).astype(np.int64)


@pytest.mark.parametrize("seed,n,res,K,kwargs", [
    (0, 150, 32, 64, {}),                        # K covers every tile
    (1, 300, 48, 96, {}),
    (11, 400, 64, 8, {}),                        # saturated K (K < overlap)
    (12, 500, 64, 4, dict(rmax=14.0, invalid_frac=0.0)),   # heavy ties at K
    (13, 40, 128, 16, dict(rmax=2.0)),           # mostly EMPTY tiles
    (14, 200, 64, 16, dict(invalid_frac=0.6)),   # many dead splats
])
def test_sorted_assignment_bit_identical_to_dense(seed, n, res, K, kwargs):
    """Full-budget sorted == dense on EVERYTHING: indices (live and empty
    slots), scores, and the overflow counter — the contract that lets the
    sorted path replace the sweep with zero downstream change."""
    grid = TileGrid(res, res, 8, 16)
    splats = random_splats(seed, n, res, res, **kwargs)
    i_d, s_d, ov_d = assign_tiles(splats, grid, K=K, return_overflow=True)
    i_s, s_s, ov_s = assign_tiles_sorted(splats, grid, K=K,
                                         tile_budget=grid.n_tiles,
                                         return_overflow=True)
    assert int(ov_d) == 0 and int(ov_s) == 0
    np.testing.assert_array_equal(np.asarray(s_d), np.asarray(s_s))
    np.testing.assert_array_equal(np.asarray(i_d), np.asarray(i_s))
    # the dispatcher routes impl="sorted" to the same result
    i_2, s_2 = assign_tiles(splats, grid, K=K, impl="sorted",
                            tile_budget=grid.n_tiles)
    np.testing.assert_array_equal(np.asarray(i_d), np.asarray(i_2))
    np.testing.assert_array_equal(np.asarray(s_d), np.asarray(s_2))


def test_sorted_assignment_tie_break_bit_identical():
    """Duplicate depths at the K boundary: the sorted path's stable
    (depth, splat index) ranking must reproduce the dense sweep's two-key
    tie-break exactly (the same invariant the merge-order test pins for
    the dense path)."""
    res = 32
    grid = TileGrid(res, res, 8, 16)
    r = np.random.default_rng(7)
    n = 300
    depths = np.repeat(r.uniform(0.5, 5.0, n // 4), 4)[:n]   # 4-way ties
    splats = random_splats(15, n, res, res, rmax=12.0, invalid_frac=0.0)
    splats = splats._replace(depth=jnp.asarray(depths, jnp.float32))
    i_d, s_d = assign_tiles(splats, grid, K=8, block=n)
    i_s, s_s = assign_tiles_sorted(splats, grid, K=8,
                                   tile_budget=grid.n_tiles)
    np.testing.assert_array_equal(np.asarray(i_d), np.asarray(i_s))
    np.testing.assert_array_equal(np.asarray(s_d), np.asarray(s_s))


def test_sorted_auto_budget_exact_on_small_scenes():
    """The auto budget (min(T, DEFAULT_TILE_BUDGET)) covers these scenes:
    overflow 0 and full bit-identity without an explicit tile_budget."""
    for seed, n, res in [(2, 60, 64), (16, 250, 48)]:
        grid = TileGrid(res, res, 8, 16)
        splats = random_splats(seed, n, res, res, rmax=6.0)
        i_d, s_d = assign_tiles(splats, grid, K=24)
        i_s, s_s, ov = assign_tiles_sorted(splats, grid, K=24,
                                           return_overflow=True)
        assert int(ov) == 0
        np.testing.assert_array_equal(np.asarray(i_d), np.asarray(i_s))
        np.testing.assert_array_equal(np.asarray(s_d), np.asarray(s_s))


def test_sorted_budget_overflow_counter_fires():
    """A starved per-splat budget must be SURFACED, not silently wrong:
    the counter reports exactly the bbox candidate slots dropped past the
    budget (conservative superset of true hits — 0 proves exactness), and
    the truncated output stays well-formed: front-to-back scores and live
    entries that are a subset of the exact assignment's."""
    grid = TileGrid(64, 64, 8, 16)
    splats = random_splats(17, 400, 64, 64, rmax=9.0, invalid_frac=0.0)
    cnt = _bbox_tile_counts(splats, grid)
    budget = max(1, int(cnt.max()) // 2)
    i_b, s_b, ov = assign_tiles_sorted(splats, grid, K=24,
                                       tile_budget=budget,
                                       return_overflow=True)
    want = int(np.maximum(cnt - budget, 0).sum())
    assert int(ov) == want and want > 0
    s_b = np.asarray(s_b)
    assert (np.diff(s_b, axis=1) <= 1e-6).all()      # still front-to-back
    # every live (tile, splat) pair the truncated run kept is a true pair
    # of the exact run (K = N: nothing truncated on the oracle side)
    i_x, s_x = assign_tiles(splats, grid, K=400)
    exact = {(t, int(i)) for t in range(grid.n_tiles)
             for i, sc in zip(np.asarray(i_x)[t], np.asarray(s_x)[t])
             if sc > NEG / 2}
    live = s_b > NEG / 2
    got = {(t, int(i)) for t in range(grid.n_tiles)
           for i in np.asarray(i_b)[t][live[t]]}
    assert got <= exact


def test_sorted_assignment_under_vmap():
    """render_batch vmaps the assignment over views — the sorted path must
    match its own unbatched result and the dense oracle per view."""
    grid = TileGrid(48, 48, 8, 16)
    sp = [random_splats(20 + v, 250, 48, 48) for v in range(3)]
    batched = jax.tree.map(lambda *xs: jnp.stack(xs), *sp)
    f = lambda s: assign_tiles_sorted(s, grid, K=16,
                                      tile_budget=grid.n_tiles)
    idx_b, score_b = jax.vmap(f)(batched)
    for v in range(3):
        i_d, s_d = assign_tiles(sp[v], grid, K=16)
        np.testing.assert_array_equal(np.asarray(score_b[v]), np.asarray(s_d))
        np.testing.assert_array_equal(np.asarray(idx_b[v]), np.asarray(i_d))


def test_assign_impl_auto_resolution():
    """"auto" picks sorted only when it can prove it should: enough tiles
    AND a known (probed/explicit) per-splat budget lean enough to win.
    No budget in hand -> the always-exact dense sweep (a directly jitted
    building block must not silently truncate); a fat budget demotes too
    (big-splat scenes are where duplicate-and-sort loses).  Unknown impls
    fail loudly."""
    from repro.core.tiling import SORTED_BUDGET_RATIO
    T = 4 * SORTED_MIN_TILES
    ok_budget = T // SORTED_BUDGET_RATIO
    assert resolve_assign_impl("auto", SORTED_MIN_TILES - 1, 8) == "dense"
    assert resolve_assign_impl("auto", SORTED_MIN_TILES) == "dense"  # no B
    assert resolve_assign_impl("auto", T, ok_budget) == "sorted"
    assert resolve_assign_impl("auto", T, ok_budget + 1) == "dense"
    # explicit impls are never overridden by the budget
    assert resolve_assign_impl("sorted", T, T) == "sorted"
    assert resolve_assign_impl("dense", 10 ** 6) == "dense"
    assert resolve_assign_impl("sorted", 1) == "sorted"
    with pytest.raises(ValueError):
        resolve_assign_impl("radix", 64)
    with pytest.raises(ValueError):
        grid = TileGrid(32, 32, 8, 16)
        assign_tiles(random_splats(0, 10, 32, 32), grid, K=4, impl="nope")


def test_resolve_assignment_probes_and_demotes():
    """render.resolve_assignment — the shared host-loop policy: probes a
    budget over the whole rig for small-splat scenes (sorted wins), and
    demotes "auto" to dense on big-splat scenes; pinned impls keep their
    choice, explicit budgets are honored verbatim."""
    from repro.core.cameras import orbital_rig
    from repro.core.gaussians import from_points
    from repro.core.render import resolve_assignment

    r = np.random.default_rng(6)
    grid = TileGrid(256, 256, 8, 16)          # T = 512 >= SORTED_MIN_TILES
    cams = orbital_rig(3, (0.5, 0.5, 0.5), 2.6, width=256, height=256)

    def scene(n, scale):
        pts = r.uniform(0, 1, (n, 3))
        return from_points(jnp.asarray(pts, jnp.float32),
                           jnp.asarray(r.uniform(0, 1, (n, 3))),
                           init_scale=scale / n ** (1 / 3), opacity=0.8)

    small = scene(20000, 0.4)                 # tiny splats: sorted wins
    impl, budget = resolve_assignment(small, cams, grid)
    assert impl == "sorted" and budget is not None
    assert budget * 20 <= grid.n_tiles        # probed lean budget
    big = scene(300, 0.6)                     # huge splats: dense wins
    impl_b, budget_b = resolve_assignment(big, cams, grid)
    assert impl_b == "dense" and budget_b is None
    # pinned sorted keeps sorted but still gets a probed budget
    impl_s, budget_s = resolve_assignment(big, cams, grid,
                                          assign_impl="sorted")
    assert impl_s == "sorted" and budget_s is not None
    # explicit budgets pass through untouched
    impl_e, budget_e = resolve_assignment(small, cams, grid,
                                          assign_impl="sorted",
                                          assign_budget=24)
    assert (impl_e, budget_e) == ("sorted", 24)


def test_render_views_probed_budget_stays_exact_on_big_splats():
    """The app-level honesty gate: on a big-splat scene at a grid past the
    auto crossover, render_views must probe the per-splat budget from
    concrete bbox counts — demoting "auto" to the dense sweep (sorted
    cannot win there) and, when sorted is pinned, sizing the budget so the
    render stays bit-identical to the dense oracle."""
    from repro.core.cameras import orbital_rig
    from repro.core.gaussians import from_points
    from repro.core.pipeline import render_views

    r = np.random.default_rng(5)
    pts = r.uniform(0, 1, (400, 3))
    g = from_points(jnp.asarray(pts, jnp.float32),
                    jnp.asarray(r.uniform(0, 1, (400, 3))),
                    init_scale=0.5 / 400 ** (1 / 3), opacity=0.8)
    grid = TileGrid(256, 256, 8, 16)
    assert grid.n_tiles >= SORTED_MIN_TILES
    cams = orbital_rig(2, (0.5, 0.5, 0.5), 2.2, width=256, height=256)
    rgb_d, _ = render_views(g, cams, grid, K=16, assign_impl="dense")
    rgb_a, _ = render_views(g, cams, grid, K=16)                # auto
    rgb_s, _ = render_views(g, cams, grid, K=16, assign_impl="sorted")
    np.testing.assert_array_equal(rgb_a, rgb_d)
    np.testing.assert_array_equal(rgb_s, rgb_d)


def test_sorted_assignment_through_render_ref_and_interpret():
    """End-to-end: swapping assign_impl never changes the rendered tiles,
    on both the jnp oracle and the interpreted Pallas kernel."""
    from repro.core.cameras import orbital_rig, select
    from repro.core.gaussians import from_points
    from repro.core.render import render_tiles

    r = np.random.default_rng(3)
    pts = r.uniform(0, 1, (300, 3))
    g = from_points(jnp.asarray(pts, jnp.float32),
                    jnp.asarray(r.uniform(0, 1, (300, 3))), opacity=0.8)
    cams = orbital_rig(1, (0.5, 0.5, 0.5), 1.8, width=48, height=48)
    grid = TileGrid(48, 48, 8, 16)
    for impl in ("ref", "interpret"):
        t_d, _, _ = render_tiles(g, select(cams, 0), grid, K=16, impl=impl,
                                 assign_impl="dense")
        t_s, _, _ = render_tiles(g, select(cams, 0), grid, K=16, impl=impl,
                                 assign_impl="sorted",
                                 assign_budget=grid.n_tiles)
        np.testing.assert_array_equal(np.asarray(t_d), np.asarray(t_s))


def test_coarse_cull_under_vmap():
    """The batched render path vmaps assign_tiles over views."""
    grid = TileGrid(48, 48, 8, 16)
    sp = [random_splats(10 + v, 250, 48, 48) for v in range(3)]
    batched = jax.tree.map(lambda *xs: jnp.stack(xs), *sp)
    f = lambda s: assign_tiles(s, grid, K=16, coarse=2)[1]
    scores_b = jax.vmap(f)(batched)
    for v in range(3):
        np.testing.assert_array_equal(
            np.asarray(scores_b[v]), np.asarray(assign_tiles(sp[v], grid, K=16)[1]))


# ---------------------------------------------------------------------------
# >32-bit packed-key fallback (_segment_topk_sort3)
# ---------------------------------------------------------------------------


def _sparse_assign_oracle(splats, grid, K):
    """Numpy oracle over HIT tiles only: per valid splat enumerate its bbox
    tiles, apply the exact circle/rect test, then per tile sort stably by
    (depth, splat idx) and keep the first K.  Same semantics as the dense
    sweep but O(hits) instead of O(T * N), so it reaches the 65k-tile grid
    that genuinely exceeds the 32 packed key bits (where the dense sweep's
    T*N cost is prohibitive)."""
    mean = np.asarray(splats.mean2d)
    rad = np.asarray(splats.radius)
    depth = np.asarray(splats.depth)
    valid = np.asarray(splats.valid)
    per_tile = {}
    for i in np.nonzero(valid)[0]:
        x0 = int(np.clip(np.ceil((mean[i, 0] - rad[i]) / grid.tile_w) - 1,
                         0, grid.nx - 1))
        x1 = int(np.clip(np.floor((mean[i, 0] + rad[i]) / grid.tile_w),
                         0, grid.nx - 1))
        y0 = int(np.clip(np.ceil((mean[i, 1] - rad[i]) / grid.tile_h) - 1,
                         0, grid.ny - 1))
        y1 = int(np.clip(np.floor((mean[i, 1] + rad[i]) / grid.tile_h),
                         0, grid.ny - 1))
        for ty in range(y0, y1 + 1):
            for tx in range(x0, x1 + 1):
                lox, loy = tx * grid.tile_w, ty * grid.tile_h
                cx = np.clip(mean[i, 0], lox, lox + grid.tile_w)
                cy = np.clip(mean[i, 1], loy, loy + grid.tile_h)
                if ((mean[i, 0] - cx) ** 2 + (mean[i, 1] - cy) ** 2
                        <= rad[i] ** 2):
                    per_tile.setdefault(ty * grid.nx + tx, []).append(i)
    # enumeration order is splat-index ascending, so a stable depth sort
    # realizes exactly the (score desc, idx asc) two-key order
    return {t: np.array(ids)[np.argsort(depth[ids], kind="stable")][:K]
            for t, ids in per_tile.items()}


def test_sort3_fallback_exact_on_genuinely_exceeding_grid():
    """A grid/N combo whose (tile, rank) key genuinely does NOT fit 32
    bits must route to _segment_topk_sort3 and still match the exact
    assignment semantics on every hit tile (and leave the rest empty)."""
    from repro.core import tiling

    grid = TileGrid(2048, 2048, 8, 8)                 # T = 65536 -> 17 bits
    n = (1 << 15) + 1                                 # rank_bits = 16
    rank_bits = max(1, (n - 1).bit_length())
    assert grid.n_tiles.bit_length() + rank_bits > 32  # genuinely exceeding
    splats = random_splats(21, n, 2048, 2048, rmax=3.0, invalid_frac=0.05)

    # prove the dispatch really takes the fallback for THIS call
    seen = []
    orig = tiling._segment_topk_sort3

    def spy(tile, depth, *, n_tiles, K):
        seen.append(n_tiles)
        return orig(tile, depth, n_tiles=n_tiles, K=K)

    try:
        tiling._segment_topk_sort3 = spy
        budget = int(_bbox_tile_counts(splats, grid).max())
        i_s, s_s, ov = assign_tiles_sorted(splats, grid, K=8,
                                           tile_budget=budget,
                                           return_overflow=True)
    finally:
        tiling._segment_topk_sort3 = orig
    assert seen == [grid.n_tiles]
    assert int(ov) == 0
    i_s, s_s = np.asarray(i_s), np.asarray(s_s)
    depth = np.asarray(splats.depth)

    want = _sparse_assign_oracle(splats, grid, K=8)
    live = s_s > NEG / 2
    hit_tiles = np.nonzero(live.any(axis=1))[0]
    assert set(hit_tiles) == set(want)                # no phantom tiles
    assert len(want) > 100                            # scene is non-trivial
    for t, ids in want.items():
        np.testing.assert_array_equal(i_s[t][live[t]], ids)
        np.testing.assert_array_equal(s_s[t][live[t]], -depth[ids])
    # front-to-back everywhere, empty slots all NEG
    assert (np.diff(np.asarray(s_s), axis=1) <= 1e-6).all()


def test_sort3_forced_parity_sweep(monkeypatch):
    """Force EVERY packed-path call through the sort3 fallback and re-run
    the bit-identity sweep vs the dense oracle: the two top-k kernels are
    interchangeable, so fallback activation can never change results."""
    from repro.core import tiling

    calls = []

    def forced(tile, rank_of, perm, depth, *, n_tiles, K, rank_bits):
        calls.append(n_tiles)
        return tiling._segment_topk_sort3(tile, depth, n_tiles=n_tiles, K=K)

    monkeypatch.setattr(tiling, "_segment_topk_packed", forced)
    sweep = [
        (0, 150, 32, 64, {}),
        (11, 400, 64, 8, {}),                        # saturated K
        (12, 500, 64, 4, dict(rmax=14.0, invalid_frac=0.0)),  # ties at K
        (14, 200, 64, 16, dict(invalid_frac=0.6)),   # many dead splats
    ]
    for seed, n, res, K, kwargs in sweep:
        grid = TileGrid(res, res, 8, 16)
        splats = random_splats(seed, n, res, res, **kwargs)
        i_d, s_d, ov_d = assign_tiles(splats, grid, K=K, return_overflow=True)
        i_s, s_s, ov_s = assign_tiles_sorted(splats, grid, K=K,
                                             tile_budget=grid.n_tiles,
                                             return_overflow=True)
        assert int(ov_d) == 0 and int(ov_s) == 0
        np.testing.assert_array_equal(np.asarray(s_d), np.asarray(s_s))
        np.testing.assert_array_equal(np.asarray(i_d), np.asarray(i_s))
    assert len(calls) == len(sweep)                  # the forcing took

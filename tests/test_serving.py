"""Serving contract suite (PR 7 tentpole): core/serving.GSRenderServer.

Pins the four serving contracts end-to-end:

  * batched queue service == sequential single-view renders (ref AND
    interpret impls) at float-associativity tolerance;
  * a pose-bucket cache HIT is BIT-identical to the cold MISS that
    populated it — indices, scores and the final image;
  * LRU eviction and zero-budget overflow are counted, never silent, and
    degraded configs still produce finite well-formed images;
  * LOD rung selection is deterministic + monotone in camera distance,
    and load shedding serves (never drops) at the lower serving K.

Plus the two table lemmas the cache leans on: quantize_pose bucket
stability and the slice_table prefix property.
"""

import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cameras import Camera, orbital_rig, select
from repro.core.gaussians import from_points
from repro.core.render import assign_tables_jit, render
from repro.core.serving import (GSRenderServer, QueueFullError, ServeCfg,
                                build_lod_ladder, camera_distance,
                                camera_eye, lod_keep_mask, select_rung,
                                splat_impact)
from repro.core.tiling import TileGrid, quantize_pose, slice_table
from repro.data.isosurface import point_cloud_for

RES = 32
CENTER = (0.5, 0.5, 0.5)


def scene(n=400, seed=0):
    pts, cols = point_cloud_for("sphere_shell", n, seed=seed)
    g = from_points(jnp.asarray(pts), jnp.asarray(cols), opacity=0.9)
    grid = TileGrid(RES, RES, 8, 16)
    return g, grid


def mixed_rig(n_near=3, n_far=3, far_r=8.0):
    """Near orbit (rung 0) + far orbit (beyond the auto LOD threshold)."""
    near = orbital_rig(n_near, CENTER, 1.5, width=RES, height=RES)
    far = orbital_rig(n_far, CENTER, far_r, width=RES, height=RES)
    return Camera(view=jnp.concatenate([near.view, far.view]),
                  fx=jnp.concatenate([near.fx, far.fx]),
                  fy=jnp.concatenate([near.fy, far.fy]),
                  width=RES, height=RES)


def canonical(cam: Camera, bins=ServeCfg.pose_bins) -> Camera:
    """The bucket-snapped camera the server actually renders."""
    _, (v, fx, fy) = quantize_pose(cam.view, cam.fx, cam.fy, bins=bins)
    return Camera(jnp.asarray(v), jnp.float32(fx), jnp.float32(fy),
                  cam.width, cam.height)


# ---------------------------------------------------------------------------
# table lemmas
# ---------------------------------------------------------------------------


def test_quantize_pose_buckets():
    g, grid = scene()
    cam = select(orbital_rig(3, CENTER, 1.5, width=RES, height=RES), 0)
    key, (v, fx, fy) = quantize_pose(cam.view, cam.fx, cam.fy)
    # sub-half-bucket noise off the canonical (lattice) pose lands in the
    # SAME bucket (a raw pose can sit arbitrarily close to a boundary, so
    # the guarantee is per-bucket, not per-pose)
    eps = 0.4 / ServeCfg.pose_bins
    key2, _ = quantize_pose(np.asarray(v, np.float64) + eps, fx, fy)
    assert key2 == key
    # a clearly different pose lands elsewhere
    key3, _ = quantize_pose(np.asarray(v, np.float64) + 0.1, fx, fy)
    assert key3 != key
    # canonicalization is idempotent: the canonical pose is its own bucket
    key4, (v4, fx4, fy4) = quantize_pose(v, fx, fy)
    assert key4 == key
    np.testing.assert_array_equal(v4, v)
    assert (fx4, fy4) == (fx, fy)


def test_slice_table_prefix_property():
    """A depth-K table's first k columns ARE the depth-k assignment —
    bit-for-bit (total order: score desc, index asc) — so shed renders can
    slice the cached Kmax table instead of re-assigning."""
    g, grid = scene()
    cams = orbital_rig(2, CENTER, 1.5, width=RES, height=RES)
    idx16, s16, _ = assign_tables_jit(grid, 16, None, "dense", None)(g, cams)
    idx8, s8, _ = assign_tables_jit(grid, 8, None, "dense", None)(g, cams)
    sl_idx, sl_s = slice_table(np.asarray(idx16), np.asarray(s16), 8)
    np.testing.assert_array_equal(sl_idx, np.asarray(idx8))
    np.testing.assert_array_equal(sl_s, np.asarray(s8))
    with pytest.raises(ValueError):
        slice_table(np.asarray(idx16), np.asarray(s16), 32)


# ---------------------------------------------------------------------------
# batched queue service == sequential renders
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("impl", ["ref", "interpret"])
def test_serve_matches_sequential_render(impl):
    g, grid = scene()
    cfg = ServeCfg(K=16, impl=impl, max_batch=4, lod_dists=(4.0,))
    server = GSRenderServer(g, grid, cfg, center=CENTER)
    rig = mixed_rig()
    results = server.serve(rig)
    assert [r.request_id for r in results] == list(range(6))
    assert {r.rung for r in results} == {0, 1}       # mixed rig spans LOD
    for v, r in enumerate(results):
        cam = canonical(select(rig, v))
        ref = render(server.ladder[r.rung], cam, grid, K=16, impl=impl)
        np.testing.assert_allclose(r.rgb, np.asarray(ref.rgb),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(r.coverage, np.asarray(ref.coverage),
                                   rtol=1e-6, atol=1e-6)
    tel = server.telemetry()
    assert tel["requests"] == 6 and tel["shed"] == 0 == tel["rejected"]
    assert tel["assign"] == 0                        # nothing dropped


# ---------------------------------------------------------------------------
# cache: hit == miss, bit-identical; LRU honesty
# ---------------------------------------------------------------------------


def test_cache_hit_bit_identical_to_miss():
    g, grid = scene()
    server = GSRenderServer(g, grid,
                            ServeCfg(K=16, max_batch=4, lod_dists=(4.0,)),
                            center=CENTER)
    rig = mixed_rig()
    cold = server.serve(rig)
    warm = server.serve(rig)
    assert not any(r.cache_hit for r in cold)
    assert all(r.cache_hit for r in warm)
    for c, w in zip(cold, warm):
        np.testing.assert_array_equal(c.rgb, w.rgb)          # BIT-identical
        np.testing.assert_array_equal(c.coverage, w.coverage)
        assert (c.rung, c.K) == (w.rung, w.K)
    tel = server.telemetry()
    assert tel["hits"] == 6 and tel["misses"] == 6
    assert tel["evictions"] == 0 == tel["cache_overflow"]


def test_cached_table_matches_fresh_assignment():
    """The cached (T, K) table is bit-identical to a fresh assignment of
    the canonical pose — the cache stores exact tables, not approximations."""
    g, grid = scene()
    server = GSRenderServer(g, grid,
                            ServeCfg(K=16, max_batch=4, lod_dists=(4.0,)),
                            center=CENTER)
    rig = orbital_rig(2, CENTER, 1.5, width=RES, height=RES)
    server.serve(rig)
    for v in range(2):
        cam = canonical(select(rig, v))
        entry = server.cached_table(select(rig, v), rung=0)
        assert entry is not None
        cams1 = Camera(cam.view[None], cam.fx[None], cam.fy[None], RES, RES)
        idx, score, _ = assign_tables_jit(grid, 16, None, "dense",
                                          None)(server.ladder[0], cams1)
        np.testing.assert_array_equal(entry[0], np.asarray(idx)[0])
        np.testing.assert_array_equal(entry[1], np.asarray(score)[0])


def test_lru_eviction_counted_and_outputs_finite():
    g, grid = scene()
    server = GSRenderServer(g, grid,
                            ServeCfg(K=16, max_batch=4, cache_entries=1),
                            center=CENTER)
    rig = mixed_rig()
    for _ in range(2):
        results = server.serve(rig)
        assert len(results) == 6
        for r in results:
            assert r.rgb.shape == (RES, RES, 3)
            assert np.isfinite(r.rgb).all() and np.isfinite(r.coverage).all()
    tel = server.telemetry()
    assert tel["evictions"] > 0                   # starved budget: counted
    assert tel["hits"] + tel["misses"] == tel["requests"]


def test_zero_cache_budget_counts_overflow():
    g, grid = scene()
    server = GSRenderServer(g, grid,
                            ServeCfg(K=16, max_batch=4, cache_entries=0),
                            center=CENTER)
    rig = orbital_rig(3, CENTER, 1.5, width=RES, height=RES)
    for _ in range(2):
        results = server.serve(rig)
        assert all(np.isfinite(r.rgb).all() for r in results)
    tel = server.telemetry()
    assert tel["cache_overflow"] > 0              # inserts dropped: counted
    assert tel["hits"] == 0                       # nothing can ever hit
    assert tel["evictions"] == 0


# ---------------------------------------------------------------------------
# LOD ladder
# ---------------------------------------------------------------------------


def test_select_rung_monotone_deterministic():
    thresholds = (2.0, 4.0, 8.0)
    dists = np.linspace(0.0, 10.0, 101)
    rungs = [select_rung(float(d), thresholds) for d in dists]
    assert rungs == sorted(rungs)                          # monotone
    assert rungs[0] == 0 and rungs[-1] == len(thresholds)  # full range
    assert rungs == [select_rung(float(d), thresholds) for d in dists]


def test_lod_keep_mask_sizes_and_cap():
    g, _ = scene()
    n_live = int(np.asarray(g.active).sum())
    full = lod_keep_mask(g, 1.0)
    assert int(full.sum()) == n_live
    half = lod_keep_mask(g, 0.5)
    assert int(half.sum()) == int(np.ceil(0.5 * n_live))
    assert not (half & ~full).any()               # keep sets nest by impact
    capped = lod_keep_mask(g, 1.0, cap=32)
    assert int(capped.sum()) == 32
    # top-impact rows survive: the kept set's min impact >= dropped max
    imp = splat_impact(g)
    assert imp[capped].min() >= imp[full & ~capped].max()


def test_build_lod_ladder_shrinks_and_compacts():
    g, _ = scene()
    ladder = build_lod_ladder(g, (1.0, 0.4), cap=64, round_to=64)
    lives = [int(np.asarray(r.active).sum()) for r in ladder]
    assert lives[0] == int(np.asarray(g.active).sum())
    assert lives[1] == min(64, int(np.ceil(0.4 * lives[0])))
    for r in ladder:
        assert r.means.shape[0] % 64 == 0          # padded capacity
        n = int(np.asarray(r.active).sum())
        assert not np.asarray(r.active)[n:].any()  # live rows compacted front


def test_server_rung_tracks_distance():
    g, grid = scene()
    server = GSRenderServer(g, grid,
                            ServeCfg(K=16, max_batch=4, lod_dists=(4.0,)),
                            center=CENTER)
    rig = mixed_rig(n_near=2, n_far=2)
    results = server.serve(rig)
    assert [r.rung for r in results] == [0, 0, 1, 1]
    # rung selection is a pure function of distance vs the ladder
    for v, r in enumerate(results):
        d = camera_distance(select(rig, v).view, server.center)
        assert r.rung == select_rung(d, server.lod_dists)


def test_camera_eye_roundtrip():
    rig = orbital_rig(4, CENTER, 1.5, width=RES, height=RES)
    for v in range(4):
        eye = camera_eye(select(rig, v).view)
        np.testing.assert_allclose(np.linalg.norm(eye - np.asarray(CENTER)),
                                   1.5, rtol=1e-5)


# ---------------------------------------------------------------------------
# load shedding + bounded queue
# ---------------------------------------------------------------------------


def test_load_shed_serves_lower_k():
    g, grid = scene()
    cfg = ServeCfg(K=16, max_batch=4, shed_at=2, shed_rung=0)
    server = GSRenderServer(g, grid, cfg, center=CENTER)
    shed_k = int(server.schedule.k_tiers[cfg.shed_rung])
    kmax = int(server.schedule.kmax)
    assert shed_k < kmax
    rig = orbital_rig(6, CENTER, 1.5, width=RES, height=RES)
    for v in range(6):
        server.submit(select(rig, v))
    results = server.flush()
    assert len(results) == 6                       # shed, never dropped
    assert [r.shed for r in results] == [False, False, True, True, True,
                                         True]
    assert [r.K for r in results] == [kmax, kmax] + [shed_k] * 4
    tel = server.telemetry()
    assert tel["shed"] == 4 and tel["rejected"] == 0
    # a shed render is exactly the low-K render of the same canonical pose
    r = results[-1]
    cam = canonical(select(rig, 5))
    ref = render(server.ladder[r.rung], cam, grid, K=shed_k)
    np.testing.assert_allclose(r.rgb, np.asarray(ref.rgb),
                               rtol=1e-6, atol=1e-6)


def test_queue_cap_rejects_and_counts():
    g, grid = scene()
    server = GSRenderServer(g, grid,
                            ServeCfg(K=16, max_batch=4, queue_cap=2),
                            center=CENTER)
    rig = orbital_rig(3, CENTER, 1.5, width=RES, height=RES)
    server.submit(select(rig, 0))
    server.submit(select(rig, 1))
    with pytest.raises(QueueFullError):
        server.submit(select(rig, 2))
    assert server.telemetry()["rejected"] == 1
    assert len(server.flush()) == 2                # accepted work survives
    # serve() flushes before the cap: same rig, no rejection
    assert len(server.serve(rig)) == 3
    assert server.telemetry()["rejected"] == 1


def test_submit_validates_camera():
    g, grid = scene()
    server = GSRenderServer(g, grid, ServeCfg(K=16), center=CENTER)
    rig = orbital_rig(2, CENTER, 1.5, width=RES, height=RES)
    with pytest.raises(ValueError):
        server.submit(rig)                         # batched rig: use serve()
    bad = orbital_rig(1, CENTER, 1.5, width=64, height=64)
    with pytest.raises(ValueError):
        server.submit(select(bad, 0))              # grid mismatch


def test_serve_cfg_validation():
    g, grid = scene()
    with pytest.raises(ValueError):
        ServeCfg(K=16, k_ladder=(8, 4, 16)).resolved_ladder()
    with pytest.raises(ValueError):
        ServeCfg(K=16, k_ladder=(4, 8)).resolved_ladder()
    with pytest.raises(ValueError):
        GSRenderServer(g, grid, ServeCfg(K=16, shed_rung=7), center=CENTER)
    with pytest.raises(ValueError):
        GSRenderServer(g, grid,
                       ServeCfg(K=16, lod_fracs=(1.0, 0.5),
                                lod_dists=(1.0, 2.0)), center=CENTER)


def test_serve_cfg_is_hashable():
    # jit cache keys derive from cfg fields; frozen dataclass must hash
    assert hash(ServeCfg()) == hash(dataclasses.replace(ServeCfg()))

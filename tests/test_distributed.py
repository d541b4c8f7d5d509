"""Distributed GS step: shard_map correctness on forced multi-device CPU.

The key invariant: the mesh-distributed forward/step computes the SAME math
as the single-device pipeline (modulo float association) — gaussian-parallel
all-gather + pixel-parallel strips are an execution strategy, not a model
change.  Runs in a subprocess so the 8-device XLA flag doesn't leak.
"""

import os
import subprocess
import sys

import pytest

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


def test_tile_view_batches_masks_none_excludes_grid_padding():
    """masks=None means "every IMAGE pixel" — grid padding (resolution not
    a tile multiple) must be masked OFF, matching the single-device
    full-image loss, which never sees pad pixels."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.distributed import _tile_view_batches
    from repro.core.tiling import TileGrid

    grid = TileGrid(20, 12, 8, 16)      # pads to 16 x 32
    gts = np.random.default_rng(0).random((1, 2, 12, 20, 3)).astype("f4")
    gt_t, mask_t = _tile_view_batches(jnp.asarray(gts), None, grid)
    assert gt_t.shape == (2, grid.n_tiles, 3, 8, 16)
    assert mask_t.shape == (2, grid.n_tiles, 8, 16)
    assert int(mask_t.sum()) == 2 * 12 * 20      # image pixels only
    # explicit all-ones masks land on the identical tiling
    ones = jnp.ones((1, 2, 12, 20), bool)
    _, mask_t2 = _tile_view_batches(jnp.asarray(gts), ones, grid)
    np.testing.assert_array_equal(mask_t, mask_t2)


def test_trainer_refuses_explicit_mesh_axes():
    """jax.make_mesh defaults to Explicit axes; the trainer needs Auto ones
    and says so up front instead of failing inside densify's scatter."""
    import jax
    import jax.numpy as jnp

    from repro.core.cameras import orbital_rig
    from repro.core.distributed import fit_partitions, make_gs_train_step
    from repro.core.gaussians import from_points
    from repro.core.tiling import TileGrid
    from repro.core.train import GSTrainCfg
    from repro.launch.mesh import make_mesh

    explicit = jax.make_mesh((1, 1), ("part", "view"),
                             axis_types=(jax.sharding.AxisType.Explicit,) * 2)
    cfg, grid = GSTrainCfg(K=8), TileGrid(16, 16, 8, 16)
    with pytest.raises(ValueError, match="AxisType.Auto"):
        make_gs_train_step(explicit, cfg, grid, 1.0)
    g = jax.tree.map(lambda x: x[None],
                     from_points(jnp.full((4, 3), 0.5), jnp.full((4, 3), 0.5)))
    cams = orbital_rig(1, (0.5, 0.5, 0.5), 1.6, width=16, height=16)
    gts = jnp.zeros((1, 1, 16, 16, 3))
    with pytest.raises(ValueError, match="AxisType.Auto"):
        fit_partitions(g, cams, gts, None, cfg, mesh=explicit, steps=1,
                       extent=1.0, grid=grid)
    auto = make_mesh((1, 1), ("part", "view"))
    assert all(t == jax.sharding.AxisType.Auto for t in auto.axis_types)


@pytest.mark.parametrize("block", [
    max(1024, 4096 // 4),     # the step's block at view batch 4
    333,                      # does not divide N
])
def test_assign_tiles_local_matches_per_partition_dense(block):
    """The strip-local dense sweep over (Pl, N) partitions equals the
    single-device dense ``assign_tiles`` of each partition, bit for bit,
    empty slots included."""
    import jax.numpy as jnp
    import numpy as np

    from repro.core.distributed import _assign_tiles_local
    from repro.core.projection import Splats2D
    from repro.core.tiling import TileGrid, assign_tiles, tile_bounds

    grid, n, K = TileGrid(64, 48, 8, 16), 2500, 16
    r = np.random.default_rng(block)
    mean = r.uniform([-12, -12], [76, 60], (2, n, 2)).astype(np.float32)
    radius = r.uniform(0.5, 9.0, (2, n)).astype(np.float32)
    depth = r.integers(1, 40, (2, n)).astype(np.float32)   # many ties
    valid = r.uniform(size=(2, n)) > 0.1
    lo, hi = tile_bounds(grid)
    idx, score, ov = _assign_tiles_local(
        jnp.asarray(mean), jnp.asarray(radius), jnp.asarray(depth),
        jnp.asarray(valid), lo, hi, K=K, block=block, impl="dense")
    assert idx.shape == (2, grid.n_tiles, K) and int(ov) == 0
    for p in range(2):
        s = Splats2D(mean2d=jnp.asarray(mean[p]),
                     cov2d=jnp.ones((n, 3), jnp.float32),
                     depth=jnp.asarray(depth[p]),
                     rgb=jnp.zeros((n, 3), jnp.float32),
                     alpha=jnp.ones(n, jnp.float32),
                     radius=jnp.asarray(radius[p]),
                     valid=jnp.asarray(valid[p]))
        want_i, want_s = assign_tiles(s, grid, K=K, block=block,
                                      impl="dense")
        np.testing.assert_array_equal(np.asarray(idx[p]), np.asarray(want_i))
        np.testing.assert_array_equal(np.asarray(score[p]).view(np.int32),
                                      np.asarray(want_s).view(np.int32))
        assert (np.asarray(want_s) > -1e29).any()

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, r"%(src)s")
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.core.cameras import orbital_rig, select
from repro.core.distributed import (gs_shardings, make_gs_forward,
                                    make_gs_train_step)
from repro.core.gaussians import from_points
from repro.core.masking import tile_l1_dssim_loss
from repro.core.render import render_tiles
from repro.core.tiling import TileGrid
from repro.core.train import GSTrainCfg
from repro.data.isosurface import point_cloud_for
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
Pn = 2
N = 256                      # divisible by data axis
res, K = 32, 16
grid = TileGrid(res, res, 8, 16)
T = grid.n_tiles
assert T %% 2 == 0

pts, cols = point_cloud_for("sphere_shell", 2 * N)
pts, cols = pts[: 2 * N], cols[: 2 * N]
cams = orbital_rig(2, (0.5, 0.5, 0.5), 1.6, width=res, height=res)
cam = select(cams, 0)

# two partitions = two halves of the cloud (owner split irrelevant here)
g_all = from_points(jnp.asarray(pts), jnp.asarray(cols), opacity=0.8)

def part(i):
    sl = slice(i * N, (i + 1) * N)
    return jax.tree.map(lambda x: x[sl], g_all)

g_batched = jax.tree.map(lambda *xs: jnp.stack(xs), part(0), part(1))

# ---- reference: single-device per-partition renders + loss ----
ref_tiles = []
for i in range(Pn):
    tiles, _, _ = render_tiles(part(i), cam, grid, K=K, impl="ref")
    ref_tiles.append(tiles)
ref_tiles = jnp.concatenate(ref_tiles)              # (P*T, 4, th, tw)

gt = jnp.clip(ref_tiles[:, :3] + 0.05, 0, 1)
mask = jnp.ones((Pn * T, grid.tile_h, grid.tile_w), bool)
ref_loss = tile_l1_dssim_loss(ref_tiles[:, :3], gt, mask, win_size=7)

# ---- distributed: shard_map forward ----
# tolerance note: the seed pinned these at 2e-4 to absorb the tie-break
# divergence (equal-depth splats at the K boundary could differ between the
# strip-local and global top-k merges on some views).  The two-key
# (score, splat-index) merge makes assignment merge-order invariant, so the
# comparison is now float-reassociation only.
fwd = make_gs_forward(mesh, grid, K=K, impl="ref", return_tiles=True)
g_sh, opt_sh, b_sh = gs_shardings(mesh)
g_dev = jax.device_put(g_batched, g_sh)
loss, tiles = jax.jit(fwd)(g_dev, cam, gt, mask)
np.testing.assert_allclose(np.asarray(tiles), np.asarray(ref_tiles),
                           rtol=1e-6, atol=1e-6)
np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-4, atol=1e-5)
print("FWD-MATCH")

# ---- optimized variants (§Perf GS hillclimb) stay faithful ----
# strip prefilter with budget 1.0 is exact (pure reordering)
fwd_strip = make_gs_forward(mesh, grid, K=K, impl="ref", return_tiles=True,
                            strip_budget=127.0 / 128.0)
_, tiles_s = jax.jit(fwd_strip)(g_dev, cam, gt, mask)
np.testing.assert_allclose(np.asarray(tiles_s), np.asarray(ref_tiles),
                           rtol=1e-6, atol=1e-6)
# split bf16 gather: conic/rgb rounding only (image-level agreement)
fwd_split = make_gs_forward(mesh, grid, K=K, impl="ref", return_tiles=True,
                            gather_mode="split", strip_budget=127.0 / 128.0)
loss_sp, tiles_sp = jax.jit(fwd_split)(g_dev, cam, gt, mask)
err = np.abs(np.asarray(tiles_sp[:, :3]) - np.asarray(ref_tiles[:, :3]))
assert err.max() < 5e-2 and err.mean() < 2e-3, (err.max(), err.mean())
assert abs(float(loss_sp) - float(ref_loss)) < 2e-3
print("OPT-MATCH")

# ---- tiered (variable-K) forward: the strip-local occupancy binning must
# reproduce the single-device dense tiles exactly (caps cover -> exact, and
# single-device tiered == single-device dense is pinned in
# test_tiered_raster.py, so this transitively pins distributed tiered ==
# single-device tiered) ----
fwd_tier = make_gs_forward(mesh, grid, K=K, impl="ref", return_tiles=True,
                           k_tiers=(4, 8, K))
_, tiles_t = jax.jit(fwd_tier)(g_dev, cam, gt, mask)
np.testing.assert_allclose(np.asarray(tiles_t), np.asarray(ref_tiles),
                           rtol=1e-6, atol=1e-6)
# explicit static caps + strip prefilter compose with tiering
fwd_tier2 = make_gs_forward(mesh, grid, K=K, impl="ref", return_tiles=True,
                            k_tiers=(4, 8, K), tier_caps=(8, 8, 8),
                            strip_budget=127.0 / 128.0)
_, tiles_t2 = jax.jit(fwd_tier2)(g_dev, cam, gt, mask)
np.testing.assert_allclose(np.asarray(tiles_t2), np.asarray(ref_tiles),
                           rtol=1e-6, atol=1e-6)
# overflow surfacing: generous caps report 0; starved caps FIRE the counter
# instead of silently rendering dropped tiles as background
_, ov0 = jax.jit(make_gs_forward(mesh, grid, K=K, impl="ref",
                                 k_tiers=(4, 8, K),
                                 return_overflow=True))(g_dev, cam, gt, mask)
assert int(ov0["tiles"]) == 0, ov0
assert int(ov0["assign"]) == 0 and int(ov0["exchange"]) == 0, ov0
_, ov1 = jax.jit(make_gs_forward(mesh, grid, K=K, impl="ref",
                                 k_tiers=(4, 8, K), tier_caps=(1, 0, 0),
                                 return_overflow=True))(g_dev, cam, gt, mask)
assert int(ov1["tiles"]) > 0, ov1
print("TIER-MATCH")

# ---- distributed train step: loss decreases, state stays sharded ----
from repro.core.train import GSOptState
step = make_gs_train_step(mesh, GSTrainCfg(K=K, lr_colors=5e-2), grid,
                          extent=1.0, impl="ref")
tr = {k: getattr(g_batched, k) for k in
      ("means", "log_scales", "quats", "opacity_logit", "colors")}
opt = GSOptState(
    m=jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), tr),
    v=jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), tr),
    step=jnp.int32(0),
    grad_accum=jnp.zeros((Pn, N)), grad_count=jnp.zeros((Pn, N)))
opt = jax.device_put(opt, opt_sh)
batch = {"gt_tiles": jax.device_put(gt, b_sh["gt_tiles"]),
         "mask_tiles": jax.device_put(mask, b_sh["mask_tiles"]),
         "cam": cam}
g_cur, losses = g_dev, []
for i in range(8):
    g_cur, opt, l = step(g_cur, opt, batch)
    losses.append(float(l))
assert losses[-1] < losses[0], losses
assert g_cur.means.sharding.num_devices == 8
print("STEP-OK", round(losses[0], 5), "->", round(losses[-1], 5))
"""


@pytest.mark.slow
def test_distributed_matches_single_device(tmp_path):
    code = SCRIPT % {"src": SRC}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-3000:])
    assert "FWD-MATCH" in out.stdout
    assert "OPT-MATCH" in out.stdout
    assert "TIER-MATCH" in out.stdout
    assert "STEP-OK" in out.stdout


VIEWS_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import sys
sys.path.insert(0, r"%(src)s")
import jax, jax.numpy as jnp
import numpy as np

from repro.core.cameras import orbital_rig, select
from repro.core.distributed import (gs_shardings, make_gs_forward,
                                    make_gs_train_step)
from repro.core.gaussians import from_points
from repro.core.render import render_tiles
from repro.core.tiling import TileGrid
from repro.core.train import GSTrainCfg, GSOptState
from repro.data.isosurface import point_cloud_for
from repro.launch.mesh import make_mesh

mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
Pn, N, res, K, V = 2, 256, 32, 16, 3
grid = TileGrid(res, res, 8, 16)
T = grid.n_tiles

pts, cols = point_cloud_for("sphere_shell", 2 * N)
pts, cols = pts[: 2 * N], cols[: 2 * N]
cams = orbital_rig(V, (0.5, 0.5, 0.5), 1.6, width=res, height=res)
g_all = from_points(jnp.asarray(pts), jnp.asarray(cols), opacity=0.8)
part = lambda i: jax.tree.map(lambda x: x[i * N:(i + 1) * N], g_all)
g_batched = jax.tree.map(lambda *xs: jnp.stack(xs), part(0), part(1))

# reference: single-device per-view, per-partition tiles
ref = []
for v in range(V):
    per_p = [render_tiles(part(i), select(cams, v), grid, K=K, impl="ref")[0]
             for i in range(Pn)]
    ref.append(jnp.concatenate(per_p))
ref = jnp.stack(ref)                                 # (V, P*T, 4, th, tw)

gt = jnp.clip(ref[:, :, :3] + 0.05, 0, 1)
mask = jnp.ones((V, Pn * T, grid.tile_h, grid.tile_w), bool)
cam_b = select(cams, jnp.arange(V))

# ---- view-batched forward: tiles per view match the per-view reference ----
fwd = make_gs_forward(mesh, grid, K=K, impl="ref", return_tiles=True, views=V)
g_sh, _, b_sh = gs_shardings(mesh, views=V)
g_dev = jax.device_put(g_batched, g_sh)
loss, tiles = jax.jit(fwd)(g_dev, cam_b,
                           jax.device_put(gt, b_sh["gt_tiles"]),
                           jax.device_put(mask, b_sh["mask_tiles"]))
np.testing.assert_allclose(np.asarray(tiles), np.asarray(ref),
                           rtol=1e-6, atol=1e-6)
print("VFWD-MATCH")

# tiered dispatch under the view fold: per-(view, partition, strip) binning
# must still reproduce the per-view dense tiles exactly
fwd_t = make_gs_forward(mesh, grid, K=K, impl="ref", return_tiles=True,
                        views=V, k_tiers=(4, 8, K))
_, tiles_t = jax.jit(fwd_t)(g_dev, cam_b, gt, mask)
np.testing.assert_allclose(np.asarray(tiles_t), np.asarray(ref),
                           rtol=1e-6, atol=1e-6)
print("VTIER-MATCH")

# heterogeneous per-view masks: the loss must be the MEAN of per-view
# losses (train.py's equal-view weighting), not a pixel-count-weighted pool
from repro.core.masking import tile_l1_dssim_loss
mask_h = mask.at[0].set(False).at[0, :, :2].set(True)   # view 0 nearly empty
loss_h = jax.jit(make_gs_forward(mesh, grid, K=K, impl="ref", views=V))(
    g_dev, cam_b, gt, mask_h)
want = np.mean([float(tile_l1_dssim_loss(ref[v][:, :3], gt[v], mask_h[v],
                                         win_size=7)) for v in range(V)])
np.testing.assert_allclose(float(loss_h), want, rtol=1e-4, atol=1e-5)
print("VLOSS-MEAN")

# perf variants stay faithful under the view axis
fwd_s = make_gs_forward(mesh, grid, K=K, impl="ref", return_tiles=True,
                        views=V, strip_budget=127.0 / 128.0)
_, tiles_s = jax.jit(fwd_s)(g_dev, cam_b, gt, mask)
np.testing.assert_allclose(np.asarray(tiles_s), np.asarray(ref),
                           rtol=1e-6, atol=1e-6)
fwd_sp = make_gs_forward(mesh, grid, K=K, impl="ref", return_tiles=True,
                         views=V, gather_mode="split")
_, tiles_sp = jax.jit(fwd_sp)(g_dev, cam_b, gt, mask)
err = np.abs(np.asarray(tiles_sp[:, :, :3]) - np.asarray(ref[:, :, :3]))
assert err.max() < 5e-2, err.max()
print("VOPT-MATCH")

# ---- view-batched train step: loss decreases, state stays sharded ----
step = make_gs_train_step(mesh, GSTrainCfg(K=K, lr_colors=5e-2), grid,
                          extent=1.0, impl="ref", views=V)
_, opt_sh, _ = gs_shardings(mesh, views=V)
tr = {k: getattr(g_batched, k) for k in
      ("means", "log_scales", "quats", "opacity_logit", "colors")}
opt = GSOptState(
    m=jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), tr),
    v=jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), tr),
    step=jnp.int32(0),
    grad_accum=jnp.zeros((Pn, N)), grad_count=jnp.zeros((Pn, N)))
opt = jax.device_put(opt, opt_sh)
batch = {"gt_tiles": jax.device_put(gt, b_sh["gt_tiles"]),
         "mask_tiles": jax.device_put(mask, b_sh["mask_tiles"]),
         "cam": cam_b}
g_cur, losses = g_dev, []
for i in range(8):
    g_cur, opt, l = step(g_cur, opt, batch)
    losses.append(float(l))
assert losses[-1] < losses[0], losses
assert g_cur.means.sharding.num_devices == 8
print("VSTEP-OK", round(losses[0], 5), "->", round(losses[-1], 5))
"""


@pytest.mark.slow
def test_view_batched_distributed_matches_per_view(tmp_path):
    """views=V path: vmapped projection + view-axis fold must reproduce the
    per-view single-device tiles, under all gather/strip variants."""
    code = VIEWS_SCRIPT % {"src": SRC}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-3000:])
    assert "VFWD-MATCH" in out.stdout
    assert "VTIER-MATCH" in out.stdout
    assert "VLOSS-MEAN" in out.stdout
    assert "VOPT-MATCH" in out.stdout
    assert "VSTEP-OK" in out.stdout


MESH2D_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, r"%(src)s")
import jax, jax.numpy as jnp
import numpy as np

from repro.core.cameras import orbital_rig, select
from repro.core.distributed import (gs_shardings, make_gs_forward,
                                    make_gs_train_step)
from repro.core.gaussians import from_points
from repro.core.masking import tile_l1_dssim_loss
from repro.core.render import render_tiles
from repro.core.tiling import TileGrid
from repro.core.train import GSTrainCfg, GSOptState, group_lrs
from repro.data.isosurface import point_cloud_for
from repro.launch.mesh import make_mesh

Pn, N, res, K, V = 2, 256, 32, 16, 2
grid = TileGrid(res, res, 8, 16)
T = grid.n_tiles
pts, cols = point_cloud_for("sphere_shell", 2 * N)
pts, cols = pts[: 2 * N], cols[: 2 * N]
cams = orbital_rig(V, (0.5, 0.5, 0.5), 1.6, width=res, height=res)
cam_b = select(cams, jnp.arange(V))
g_all = from_points(jnp.asarray(pts), jnp.asarray(cols), opacity=0.8)
part = lambda i: jax.tree.map(lambda x: x[i * N:(i + 1) * N], g_all)
g_b = jax.tree.map(lambda *xs: jnp.stack(xs), part(0), part(1))

ref = []
for v in range(V):
    per_p = [render_tiles(part(i), select(cams, v), grid, K=K, impl="ref")[0]
             for i in range(Pn)]
    ref.append(jnp.concatenate(per_p))
ref = jnp.stack(ref)                                 # (V, P*T, 4, th, tw)
gt = jnp.clip(ref[:, :, :3] + 0.05, 0, 1)
mask = jnp.ones((V, Pn * T, grid.tile_h, grid.tile_w), bool)

mesh2d = make_mesh((2, 2), ("part", "view"))
mesh1d = make_mesh((2,), ("part",))
cfg = GSTrainCfg(K=K, lr_colors=5e-2)

# ---- 2-D forward: view-sharded tiles/loss match the per-view reference,
# tiered on, overflow 0 ----
fwd = make_gs_forward(mesh2d, grid, K=K, impl="ref", return_tiles=True,
                      views=V, k_tiers=(4, 8, K), return_overflow=True)
g_sh, opt_sh, b_sh = gs_shardings(mesh2d, views=V)
g_dev = jax.device_put(g_b, g_sh)
loss, tiles, ov = jax.jit(fwd)(g_dev,
                               jax.device_put(cam_b, b_sh["cam"]),
                               jax.device_put(gt, b_sh["gt_tiles"]),
                               jax.device_put(mask, b_sh["mask_tiles"]))
np.testing.assert_allclose(np.asarray(tiles), np.asarray(ref),
                           rtol=1e-6, atol=1e-6)
want = np.mean([float(tile_l1_dssim_loss(ref[v][:, :3], gt[v], mask[v],
                                         win_size=7)) for v in range(V)])
np.testing.assert_allclose(float(loss), want, rtol=1e-4, atol=1e-5)
assert int(ov["tiles"]) == 0, ov
print("M2D-FWD-MATCH")

# ---- single-device reference STEP: same tile loss + Adam math, by hand ----
def ref_step(kt):
    lrs = group_lrs(cfg, 1.0)
    def loss_fn(tr):
        g = g_b.with_trainable(tr)
        ls = []
        for v in range(V):
            per_p = [render_tiles(jax.tree.map(lambda x: x[i], g),
                                  select(cams, v), grid, K=K, impl="ref",
                                  k_tiers=kt)[0] for i in range(Pn)]
            t = jnp.concatenate(per_p)
            ls.append(tile_l1_dssim_loss(t[:, :3], gt[v], mask[v],
                                         win_size=7))
        return jnp.stack(ls).mean()
    tr = {k: getattr(g_b, k) for k in
          ("means", "log_scales", "quats", "opacity_logit", "colors")}
    loss, grads = jax.value_and_grad(loss_fn)(tr)
    out = {}
    for k in tr:
        gr = grads[k].astype(jnp.float32)
        m = (1 - cfg.b1) * gr
        v_ = (1 - cfg.b2) * gr * gr
        d = (m / (1 - cfg.b1)) / (jnp.sqrt(v_ / (1 - cfg.b2)) + cfg.eps)
        out[k] = tr[k] - lrs[k] * d
    return {k: np.asarray(x) for k, x in out.items()}, float(loss)

def dist_step(mesh, kt, step_cfg=None):
    step = make_gs_train_step(mesh, step_cfg or cfg, grid, extent=1.0,
                              impl="ref", views=V, k_tiers=kt)
    gsh, osh, bsh = gs_shardings(mesh, views=V)
    tr = {k: getattr(g_b, k) for k in
          ("means", "log_scales", "quats", "opacity_logit", "colors")}
    opt = GSOptState(
        m=jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), tr),
        v=jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), tr),
        step=jnp.int32(0),
        grad_accum=jnp.zeros((Pn, N)), grad_count=jnp.zeros((Pn, N)))
    batch = {"gt_tiles": jax.device_put(gt, bsh["gt_tiles"]),
             "mask_tiles": jax.device_put(mask, bsh["mask_tiles"]),
             "cam": jax.device_put(cam_b, bsh["cam"])}
    g1, _, l = step(jax.device_put(g_b, gsh), jax.device_put(opt, osh),
                    batch)
    return {k: np.asarray(x) for k, x in g1.trainable().items()}, float(l)

# the key invariant: sharding the view axis is an execution strategy, not a
# model change — 2-D mesh step == 1-D mesh step == single-device step,
# dense AND tiered
for kt in (None, (4, 8, K)):
    r, rl = ref_step(kt)
    p1, l1 = dist_step(mesh1d, kt)
    p2, l2 = dist_step(mesh2d, kt)
    for k in r:
        np.testing.assert_allclose(p1[k], r[k], rtol=1e-6, atol=1e-6,
                                   err_msg=f"1-D mesh {k} kt={kt}")
        np.testing.assert_allclose(p2[k], r[k], rtol=1e-6, atol=1e-6,
                                   err_msg=f"2-D mesh {k} kt={kt}")
    np.testing.assert_allclose([l1, l2], rl, rtol=1e-5, atol=1e-6)
print("M2D-STEP-MATCH")

# sort-based strip-local assignment == dense sweep through the FULL 2-D
# mesh step (params after one Adam update at 1e-6; the two impls share the
# two-key tie-break, so the assignment itself is bit-identical and the
# only differences left are float reassociation downstream)
for kt in (None, (4, 8, K)):
    p_sd, l_sd = dist_step(mesh2d, kt,
                           GSTrainCfg(K=K, lr_colors=5e-2,
                                      assign_impl="sorted"))
    p_dn, l_dn = dist_step(mesh2d, kt,
                           GSTrainCfg(K=K, lr_colors=5e-2,
                                      assign_impl="dense"))
    for k in p_sd:
        np.testing.assert_allclose(p_sd[k], p_dn[k], rtol=1e-6, atol=1e-6,
                                   err_msg=f"sorted-vs-dense {k} kt={kt}")
    np.testing.assert_allclose(l_sd, l_dn, rtol=1e-6, atol=1e-7)
print("M2D-ASSIGN-SORTED")

# tiered-by-DEFAULT cfg (k_tiers resolved from GSTrainCfg, caps fall back
# to the always-exact strip size) must equal the dense escape hatch
p_auto, _ = dist_step(mesh2d, cfg.resolved_k_tiers())
cfg_dense = GSTrainCfg(K=K, lr_colors=5e-2, dense_k=K)
assert cfg_dense.resolved_k_tiers() is None
step_d = make_gs_train_step(mesh2d, cfg_dense, grid, extent=1.0,
                            impl="ref", views=V)
gsh, osh, bsh = gs_shardings(mesh2d, views=V)
tr = {k: getattr(g_b, k) for k in
      ("means", "log_scales", "quats", "opacity_logit", "colors")}
opt = GSOptState(
    m=jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), tr),
    v=jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), tr),
    step=jnp.int32(0),
    grad_accum=jnp.zeros((Pn, N)), grad_count=jnp.zeros((Pn, N)))
batch = {"gt_tiles": jax.device_put(gt, bsh["gt_tiles"]),
         "mask_tiles": jax.device_put(mask, bsh["mask_tiles"]),
         "cam": jax.device_put(cam_b, bsh["cam"])}
g_d, _, _ = step_d(jax.device_put(g_b, gsh), jax.device_put(opt, osh),
                   batch)
for k, x in g_d.trainable().items():
    np.testing.assert_allclose(p_auto[k], np.asarray(x),
                               rtol=1e-6, atol=1e-6, err_msg=k)
print("M2D-DEFAULT-TIERED")

# odd views must be rejected loudly, not silently truncated
try:
    make_gs_forward(mesh2d, grid, K=K, impl="ref", views=3)
except ValueError as e:
    assert "view" in str(e)
    print("M2D-DIVISIBILITY")
"""


@pytest.mark.slow
def test_2d_mesh_step_matches_1d_and_single_device(tmp_path):
    """The ("part", "view") 2-D mesh: view-sharded forward tiles/loss match
    the per-view reference, and the train step (params after one Adam
    update) matches the 1-D mesh and a hand-built single-device step at
    1e-6 — dense and tiered, overflow 0, tiered-by-default cfg included —
    and the sort-based strip assignment (cfg.assign_impl="sorted") matches
    the dense sweep through the full 2-D step at 1e-6."""
    code = MESH2D_SCRIPT % {"src": SRC}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-3000:])
    assert "M2D-FWD-MATCH" in out.stdout
    assert "M2D-STEP-MATCH" in out.stdout
    assert "M2D-ASSIGN-SORTED" in out.stdout
    assert "M2D-DEFAULT-TIERED" in out.stdout
    assert "M2D-DIVISIBILITY" in out.stdout


DRIVER_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, r"%(src)s")
import jax, jax.numpy as jnp
import numpy as np

from repro.core.cameras import orbital_rig
from repro.core.distributed import fit_partitions
from repro.core.gaussians import from_points
from repro.core.pipeline import render_views
from repro.core.tiling import TileGrid
from repro.core.train import GSTrainCfg, fit_partition
from repro.data.isosurface import point_cloud_for
from repro.runtime import CheckpointManager
from repro.launch.mesh import make_mesh

N, res, V = 256, 32, 4
pts, cols = point_cloud_for("sphere_shell", N)
pts, cols = pts[:N], cols[:N]
cams = orbital_rig(V, (0.5, 0.5, 0.5), 1.6, width=res, height=res)
mesh = make_mesh((2, 2), ("part", "view"))
grid = TileGrid(res, res, 8, 16)

# GT rendered at bg=0: the distributed tile loss compares RAW premultiplied
# color tiles (no background composite), so the single-device reference
# must train with bg=0 too
g_gt = from_points(jnp.asarray(pts), jnp.asarray(cols), opacity=0.95)
gts = jnp.asarray(render_views(g_gt, cams, grid, K=16, bg=0.0)[0])
masks = jnp.ones((V, res, res), bool)
g0 = from_points(jnp.asarray(pts), jnp.asarray(cols), capacity=N + 128,
                 opacity=0.7)
g_b = jax.tree.map(lambda x: x[None], g0)           # (P=1, N) batched

def check(tag, single, dist, atol=None):
    gs_1, _, l1 = single
    gs_2, _, l2 = dist
    np.testing.assert_allclose(l1, l2, rtol=1e-5, atol=1e-6, err_msg=tag)
    for k, v in gs_1.trainable().items():
        np.testing.assert_allclose(
            np.asarray(v), np.asarray(getattr(gs_2, k))[0],
            rtol=1e-6, atol=(atol or {}).get(k, 1e-6),
            err_msg=f"{tag}:{k}")
    assert int(np.asarray(gs_1.active).sum()) \
        == int(np.asarray(gs_2.active).sum()), tag
    print(tag, [round(l, 5) for l in l2])

# ---- TierSchedule lifecycle parity: probe -> train -> densify -> re-probe
# on the 2-D mesh == fit_partition's single-device loop, step for step.
# lambda_dssim=0 isolates the masked-L1 term, which is tile-layout
# invariant (the D-SSIM term is per-tile windowed by construction on the
# distributed path — pinned separately below on a one-tile grid).  A
# trajectory match at 1e-6 through two densify events also proves the
# probed caps never overflowed (a dropped tile would shift the loss).
cfg = GSTrainCfg(K=16, lambda_dssim=0.0, bg=0.0, view_batch=2,
                 lr_colors=5e-2, max_new=64, densify_grad_thresh=1e-9)
kw = dict(steps=6, extent=1.0, densify_every=3, densify_from=0, grid=grid)
# quats atol 3e-6: reduction order.  The "view" axis sums the two views'
# gradients with a cross-device psum instead of inside one device; the
# same lifecycle on a (2, 1) mesh is bit-exact, while (1, 2) and (2, 2)
# both leave 1 of 1536 quats elements off by 2.1e-6 after 6 Adam steps
# (every other element within 1e-6).
check("TIERED-LIFECYCLE-PARITY",
      fit_partition(g0, cams, gts, masks, cfg, key=jax.random.PRNGKey(1),
                    **kw),
      fit_partitions(g_b, cams, gts[None], masks[None], cfg, mesh=mesh,
                     key=jax.random.PRNGKey(1), **kw),
      atol={"quats": 3e-6})

# ---- dense escape hatch: same driver loop, no schedule ----
cfg_d = GSTrainCfg(K=16, dense_k=16, lambda_dssim=0.0, bg=0.0,
                   view_batch=2, lr_colors=5e-2)
assert cfg_d.tier_schedule() is None
kw = dict(steps=3, extent=1.0, grid=grid)
check("DENSE-PARITY",
      fit_partition(g0, cams, gts, masks, cfg_d, key=jax.random.PRNGKey(3),
                    **kw),
      fit_partitions(g_b, cams, gts[None], masks[None], cfg_d, mesh=mesh,
                     key=jax.random.PRNGKey(3), **kw))

# ---- full loss (L1 + D-SSIM): a single tile covering the image makes the
# per-tile windowed D-SSIM identical to gs_loss's full-image win-11 SSIM,
# so the complete loss trajectory must match too ----
grid1 = TileGrid(res, res, res, res)
cfg1 = GSTrainCfg(K=16, lambda_dssim=0.2, bg=0.0, view_batch=2,
                  tile_h=res, tile_w=res, lr_colors=5e-2)
kw = dict(steps=3, extent=1.0, grid=grid1)
check("FULL-LOSS-PARITY",
      fit_partition(g0, cams, gts, masks, cfg1, key=jax.random.PRNGKey(2),
                    **kw),
      fit_partitions(g_b, cams, gts[None], masks[None], cfg1, mesh=mesh,
                     key=jax.random.PRNGKey(2), win_size=11, **kw))

# ---- checkpoint/resume: an interrupted driver run resumes with the saved
# schedule (no re-probe) and reproduces the uninterrupted loss curve ----
import tempfile
cfg = GSTrainCfg(K=16, lambda_dssim=0.0, bg=0.0, view_batch=2,
                 lr_colors=5e-2, max_new=64, densify_grad_thresh=1e-9)
kw = dict(mesh=mesh, extent=1.0, densify_every=3, densify_from=0, grid=grid)
ck_a = CheckpointManager(tempfile.mkdtemp(), keep=0)
_, _, full = fit_partitions(g_b, cams, gts[None], masks[None], cfg,
                            key=jax.random.PRNGKey(1), steps=6,
                            ckpt=ck_a, ckpt_every=3, **kw)
ck_b = CheckpointManager(tempfile.mkdtemp(), keep=0)
sched_b = cfg.tier_schedule()
fit_partitions(g_b, cams, gts[None], masks[None], cfg,
               key=jax.random.PRNGKey(1), steps=3, ckpt=ck_b,
               ckpt_every=3, schedule=sched_b, **kw)
saved_caps = sched_b.tier_caps
sched_c = cfg.tier_schedule()
g_r, _, resumed = fit_partitions(
    g_b, cams, gts[None], masks[None], cfg, key=jax.random.PRNGKey(1),
    steps=6, ckpt=ck_b, ckpt_every=3, schedule=sched_c, **kw)
assert len(resumed) == 3, resumed
np.testing.assert_allclose(resumed, full[3:], rtol=1e-6, atol=1e-7)
print("DRIVER-RESUME-MATCH", [round(l, 5) for l in resumed])
"""


@pytest.mark.slow
def test_distributed_driver_matches_fit_partition(tmp_path):
    """The distributed tier-schedule driver (core.distributed.fit_partitions)
    on the 4-device ("part", "view") mesh reproduces the single-device
    fit_partition trajectory at 1e-6 — tiered (full probe/densify/re-probe
    lifecycle) and dense, L1-only and full loss (one-tile grid, win-11
    D-SSIM == full-image gs_loss) — and resumes from a mid-run checkpoint
    onto the uninterrupted loss curve without re-probing."""
    code = DRIVER_SCRIPT % {"src": SRC}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-3000:])
    assert "TIERED-LIFECYCLE-PARITY" in out.stdout
    assert "DENSE-PARITY" in out.stdout
    assert "FULL-LOSS-PARITY" in out.stdout
    assert "DRIVER-RESUME-MATCH" in out.stdout


@pytest.mark.slow
def test_gs_cli_driver_smoke_and_resume(tmp_path):
    """`python -m repro.launch.train --gs --smoke` on 4 forced host devices
    runs the full partition -> tiered distributed training -> checkpoint ->
    merge -> render lifecycle, and a second invocation resumes from the
    saved checkpoint (restored TierSchedule, no re-probe)."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    base = [sys.executable, "-m", "repro.launch.train", "--gs", "--smoke",
            "--host-devices", "4", "--ckpt-dir", str(tmp_path)]
    out = subprocess.run(base + ["--steps", "2"], env=env,
                         capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-3000:])
    assert "raster=tiered" in out.stdout
    assert "PSNR" in out.stdout
    out2 = subprocess.run(base + ["--steps", "3"], env=env,
                          capture_output=True, text=True, timeout=900)
    assert out2.returncode == 0, (out2.stdout[-2000:], out2.stderr[-3000:])
    assert "resuming from checkpoint step 2" in out2.stdout
    assert "PSNR" in out2.stdout


def test_exchange_schedule_probe_growth_and_state():
    """ExchangeSchedule follows the TierSchedule honesty contract host-side:
    probed budgets carry slack and rounding, overflow grows them
    geometrically (clamped at n_local, where truncation is impossible),
    and the state round-trips through the checkpoint payload."""
    from repro.core.distributed import ExchangeSchedule

    es = ExchangeSchedule()
    assert es.budget is None
    # no probe yet -> overflow is a no-op (nothing to grow)
    assert es.note_overflow(5, 128) is False
    # probe: ceil(121 * 1.5) = 182 -> round to 192 -> clamp at n_local
    assert es.probe_budget(121, 128) == 128
    assert es.probe_budget(10, 512) == 16          # slack + round_to floor
    # geometric growth on a real counter; 0 never grows
    assert es.note_overflow(0, 512) is False and es.budget == 16
    assert es.note_overflow(7, 512) is True and es.budget == 32
    assert es.note_overflow(1, 512) and es.budget == 64
    # clamp: at n_local the budget covers every local splat -> no growth
    es.budget = 512
    assert es.note_overflow(3, 512) is False and es.budget == 512
    # state round-trip (the extra["exchange"] checkpoint payload)
    es2 = ExchangeSchedule.from_state(es.state_dict())
    assert es2.budget == 512 and es2.slack == es.slack
    pinned = ExchangeSchedule(budget=64)
    assert pinned.budget == 64
    assert "budget=64" in repr(pinned)


def test_exchange_schedule_budget_matrix():
    """The (n_part, n_part) budget matrix keeps the same honesty contract
    PER EDGE: probes size each edge independently, overflow grows only the
    starved edges, ``ensure`` is the grow-never-shrink in-step resize, the
    matrix round-trips through the JSON checkpoint payload, and malformed
    matrices are refused loudly."""
    import numpy as np
    import pytest

    from repro.core.distributed import ExchangeSchedule, check_budget_matrix

    es = ExchangeSchedule()
    demand = np.array([[40, 5], [90, 10]])
    B = es.probe_budget(demand, 512)
    # per-edge: ceil(d * 1.5) rounded up to 16 -> [[64, 16], [144, 16]]
    np.testing.assert_array_equal(B, [[64, 16], [144, 16]])

    # overflow on one edge grows ONLY that edge (geometric, clamped)
    ov = np.zeros((2, 2), np.int64)
    ov[0, 1] = 3
    assert es.note_overflow(ov, 512) is True
    B2 = np.asarray(es.budget)
    assert B2[0, 1] == 32
    B_ref = np.array([[64, 32], [144, 16]])
    np.testing.assert_array_equal(B2, B_ref)
    assert es.note_overflow(np.zeros((2, 2)), 512) is False

    # a SCALAR counter against a matrix budget (older telemetry) grows
    # every edge — conservative, never silent
    es_sc = ExchangeSchedule.from_state(es.state_dict())
    assert es_sc.note_overflow(1, 512) is True
    assert (np.asarray(es_sc.budget) >= B_ref).all()

    # ensure: grow-never-shrink to cover a demand bound, no slack
    assert es.ensure(np.full((2, 2), 100), 512) is True
    np.testing.assert_array_equal(np.asarray(es.budget),
                                  np.maximum(B_ref, 112))
    assert es.ensure(np.full((2, 2), 1), 512) is False    # never shrinks

    # round-trip: nested-list JSON payload -> identical matrix + key
    es2 = ExchangeSchedule.from_state(es.state_dict())
    np.testing.assert_array_equal(np.asarray(es2.budget),
                                  np.asarray(es.budget))
    assert es2.budget_key() == es.budget_key()
    assert isinstance(es2.budget_key(), tuple)
    assert "2x2[" in repr(es2)

    # loud validation: non-square, wrong-size and non-positive matrices
    with pytest.raises(ValueError, match="square"):
        check_budget_matrix(np.ones((2, 3)))
    with pytest.raises(ValueError, match="refused"):
        check_budget_matrix(np.ones((2, 2)), 4)
    with pytest.raises(ValueError, match="refused"):
        check_budget_matrix(np.ones((8, 8)), 4)
    with pytest.raises(ValueError):
        check_budget_matrix(np.zeros((2, 2)))
    with pytest.raises(ValueError):
        ExchangeSchedule(budget=np.ones((2, 3)))


def test_window_assignment():
    """The overlap-aware window assignment is a deterministic permutation
    that parks each brick's dominant band on the free local shift: when a
    derangement's edges carry the heavy overlap, tau recovers it and the
    ladder cost collapses to the light residue; with nothing to gain it
    stays the identity."""
    import numpy as np

    from repro.core.distributed import window_assignment

    # uniform overlap: no assignment beats another — identity, both sizes
    np.testing.assert_array_equal(window_assignment(np.full((4, 4), 7)),
                                  np.arange(4))
    np.testing.assert_array_equal(window_assignment(np.ones((1, 1))), [0])

    n = 8
    sigma = np.roll(np.arange(n), 3)       # heavy edges all on one shift
    rng = np.random.default_rng(0)
    B = rng.integers(1, 8, (n, n))
    B[np.arange(n), sigma] = 500
    tau = window_assignment(B)
    assert sorted(tau) == list(range(n)), tau          # a permutation
    shifts = [(np.arange(n) + k) % n for k in range(1, n)]

    def cost(t):
        return sum(int(B[np.arange(n), t[s]].max()) for s in shifts)

    np.testing.assert_array_equal(tau, sigma)
    assert cost(tau) + 400 < cost(np.arange(n)), (cost(tau), cost(sigma))
    np.testing.assert_array_equal(tau, window_assignment(B))  # deterministic


EXCHANGE_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, r"%(src)s")
import jax, jax.numpy as jnp
import numpy as np

from repro.core.cameras import orbital_rig, select
from repro.core.distributed import (ExchangeSchedule, gs_shardings,
                                    make_gs_exchange_probe, make_gs_forward,
                                    make_gs_train_step, probe_gs_exchange)
from repro.core.gaussians import from_points
from repro.core.tiling import TileGrid
from repro.core.train import GSTrainCfg, GSOptState
from repro.data.isosurface import point_cloud_for
from repro.launch.mesh import make_mesh

Pn, N, res, K, V = 2, 256, 32, 16, 2
grid = TileGrid(res, res, 8, 16)
T = grid.n_tiles
pts, cols = point_cloud_for("sphere_shell", 2 * N)
pts, cols = pts[: 2 * N], cols[: 2 * N]
cams = orbital_rig(V, (0.5, 0.5, 0.5), 1.6, width=res, height=res)
cam_b = select(cams, jnp.arange(V))
g_all = from_points(jnp.asarray(pts), jnp.asarray(cols), opacity=0.8)
part = lambda i: jax.tree.map(lambda x: x[i * N:(i + 1) * N], g_all)
g_b = jax.tree.map(lambda *xs: jnp.stack(xs), part(0), part(1))

mesh2d = make_mesh((2, 2), ("part", "view"))
mesh1d = make_mesh((4,), ("part",))
g_sh, opt_sh, b_sh = gs_shardings(mesh2d, views=V)
g_dev = jax.device_put(g_b, g_sh)
cam_dev = jax.device_put(cam_b, b_sh["cam"])
gt = jnp.zeros((V, Pn * T, 3, grid.tile_h, grid.tile_w))
mask = jnp.ones((V, Pn * T, grid.tile_h, grid.tile_w), bool)
gt_dev = jax.device_put(gt, b_sh["gt_tiles"])
mask_dev = jax.device_put(mask, b_sh["mask_tiles"])

# ---- edge-budget probe: pmax'd worst overlap, sized with slack ----
es = ExchangeSchedule()
E = probe_gs_exchange(es, mesh2d, grid, g_dev, cam_dev, views=V)
assert 1 <= E <= N // 2, E
raw = int(jax.jit(make_gs_exchange_probe(mesh2d, grid, views=V))(
    g_dev, cam_dev))
assert E >= min(raw, N // 2), (E, raw)
print("EX-PROBE", E, raw)

# ---- per-edge probe: the (n, n) demand matrix agrees with the scalar
# probe (its max IS the worst edge) and sizes a matrix budget ----
esm = ExchangeSchedule()
B = probe_gs_exchange(esm, mesh2d, grid, g_dev, cam_dev, views=V,
                      per_edge=True)
raw_m = np.asarray(jax.jit(make_gs_exchange_probe(
    mesh2d, grid, views=V, per_edge=True))(g_dev, cam_dev))
assert raw_m.shape == (2, 2) and int(raw_m.max()) == raw, (raw_m, raw)
assert (np.asarray(B) >= np.minimum(raw_m, N // 2)).all(), (B, raw_m)
print("EX-PROBE-EDGES", raw_m.tolist())

# ---- forward parity vs the all-gather table, dense AND tiered: identical
# tiles at 1e-6 (the received table is an order-preserving subsequence of
# the gathered table, so the two-key top-k selects identical splats) and a
# zero overflow dict ----
for kt in (None, (4, 8, K)):
    fg = make_gs_forward(mesh2d, grid, K=K, impl="ref", views=V, k_tiers=kt,
                         return_tiles=True, return_overflow=True)
    lg, tg, og = jax.jit(fg)(g_dev, cam_dev, gt_dev, mask_dev)
    for eb in (E, B):   # scalar all_to_all AND ragged per-edge ladder
        fe = make_gs_forward(mesh2d, grid, K=K, impl="ref", views=V,
                             k_tiers=kt, return_tiles=True,
                             return_overflow=True,
                             exchange=True, exchange_budget=eb)
        le, te, oe = jax.jit(fe)(g_dev, cam_dev, gt_dev, mask_dev)
        assert int(oe["exchange"]) == 0 and int(oe["tiles"]) == 0, oe
        if np.ndim(eb) == 2:
            # matrix telemetry: zero per-edge drops, and the in-step
            # demand matrix IS the host probe's measurement
            assert (np.asarray(oe["exchange_edges"]) == 0).all(), oe
            np.testing.assert_array_equal(
                np.asarray(oe["exchange_demand"]), raw_m)
        np.testing.assert_allclose(np.asarray(te).reshape(tg.shape),
                                   np.asarray(tg), rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(float(le), float(lg),
                                   rtol=1e-6, atol=1e-7)
print("EX-FWD-MATCH")

# ---- 1-D ("part",) x4 mesh: the window splits 4 ways (sub = T // 4) and
# the exchange must still match its own gather step — scalar and per-edge
# matrix budgets alike ----
g_sh1, opt_sh1, b_sh1 = gs_shardings(mesh1d, views=V)
es4 = ExchangeSchedule()
B4 = probe_gs_exchange(es4, mesh1d, grid,
                       jax.device_put(g_b, g_sh1),
                       jax.device_put(cam_b, b_sh1["cam"]),
                       views=V, per_edge=True)
fwd_tri = []
for eb in (None, E, B4):
    f = make_gs_forward(mesh1d, grid, K=K, impl="ref", views=V,
                        k_tiers=(4, 8, K), return_overflow=True,
                        exchange=eb is not None, exchange_budget=eb)
    l, ov = jax.jit(f)(jax.device_put(g_b, g_sh1),
                       jax.device_put(cam_b, b_sh1["cam"]),
                       jax.device_put(gt, b_sh1["gt_tiles"]),
                       jax.device_put(mask, b_sh1["mask_tiles"]))
    assert int(ov["exchange"]) == 0 and int(ov["tiles"]) == 0, ov
    fwd_tri.append(float(l))
np.testing.assert_allclose(fwd_tri[1], fwd_tri[0], rtol=1e-6, atol=1e-7)
np.testing.assert_allclose(fwd_tri[2], fwd_tri[0], rtol=1e-6, atol=1e-7)
print("EX-1D-MATCH")

# ---- overlap-aware window assignment: inflating a derangement's edges
# forces window_assignment to pick a non-identity band permutation inside
# the ladder; the loss partials psum across "part", so WHICH device
# renders which band must not change the loss (or fire any counter) ----
from repro.core.distributed import window_assignment
sigma = np.array([3, 2, 1, 0])
B_tau = np.asarray(B4).copy()
B_tau[np.arange(4), sigma] = N
tau = window_assignment(np.minimum(B_tau, N))
assert not (tau == np.arange(4)).all(), tau
f_tau = make_gs_forward(mesh1d, grid, K=K, impl="ref", views=V,
                        k_tiers=(4, 8, K), return_overflow=True,
                        exchange=True, exchange_budget=B_tau)
l_tau, ov_tau = jax.jit(f_tau)(jax.device_put(g_b, g_sh1),
                               jax.device_put(cam_b, b_sh1["cam"]),
                               jax.device_put(gt, b_sh1["gt_tiles"]),
                               jax.device_put(mask, b_sh1["mask_tiles"]))
assert int(ov_tau["exchange"]) == 0, ov_tau
np.testing.assert_allclose(float(l_tau), fwd_tri[0], rtol=1e-6, atol=1e-7)
print("EX-TAU-MATCH", tau.tolist())

# ---- train-step parity: params after one Adam update at 1e-6, dense and
# tiered+sorted (the sorted strip assignment composes with the exchange
# table exactly like with the gathered one) ----
def one(cfgx, kt):
    step = make_gs_train_step(mesh2d, cfgx, grid, extent=1.0, impl="ref",
                              views=V, k_tiers=kt)
    tr = {k: getattr(g_b, k) for k in
          ("means", "log_scales", "quats", "opacity_logit", "colors")}
    opt = GSOptState(
        m=jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), tr),
        v=jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), tr),
        step=jnp.int32(0),
        grad_accum=jnp.zeros((Pn, N)), grad_count=jnp.zeros((Pn, N)))
    batch = {"gt_tiles": gt_dev, "mask_tiles": mask_dev, "cam": cam_dev}
    g1, _, l = step(jax.device_put(g_b, g_sh),
                    jax.device_put(opt, opt_sh), batch)
    return {k: np.asarray(x) for k, x in g1.trainable().items()}, float(l)

for kt, ai in ((None, "dense"), ((4, 8, K), "sorted")):
    pg, lg = one(GSTrainCfg(K=K, lr_colors=5e-2, assign_impl=ai,
                            assign_budget=8 if ai == "sorted" else None), kt)
    pe, le = one(GSTrainCfg(K=K, lr_colors=5e-2, assign_impl=ai,
                            assign_budget=8 if ai == "sorted" else None,
                            exchange=True, exchange_budget=E), kt)
    for k in pg:
        np.testing.assert_allclose(pe[k], pg[k], rtol=1e-6, atol=1e-6,
                                   err_msg=f"{k} kt={kt} assign={ai}")
    np.testing.assert_allclose(le, lg, rtol=1e-6, atol=1e-7)
print("EX-STEP-MATCH")

# ---- adversarial: a starved edge budget REPORTS (psum'd counter > 0) and
# the output stays well-formed — finite loss, finite tiles, finite params
# after a step — never NaN, never a silent crash ----
fs = make_gs_forward(mesh2d, grid, K=K, impl="ref", views=V, k_tiers=None,
                     return_tiles=True, return_overflow=True,
                     exchange=True, exchange_budget=1)
ls, ts, ovs = jax.jit(fs)(g_dev, cam_dev, gt_dev, mask_dev)
assert int(ovs["exchange"]) > 0, ovs
assert np.isfinite(float(ls)) and np.isfinite(np.asarray(ts)).all()
ps, lss = one(GSTrainCfg(K=K, lr_colors=5e-2, exchange=True,
                         exchange_budget=1), None)
assert np.isfinite(lss)
assert all(np.isfinite(v).all() for v in ps.values())
print("EX-STARVED", int(ovs["exchange"]))

# ---- adversarial, per-edge: starving ONE edge of the matrix fires ONLY
# that edge's psum'd counter; every other edge stays zero and the output
# stays finite ----
B_st = np.asarray(B).copy()
B_st[0, 1] = 1
fse = make_gs_forward(mesh2d, grid, K=K, impl="ref", views=V, k_tiers=None,
                      return_overflow=True,
                      exchange=True, exchange_budget=B_st)
lse, ove = jax.jit(fse)(g_dev, cam_dev, gt_dev, mask_dev)
edges = np.asarray(ove["exchange_edges"])
assert edges[0, 1] > 0, edges
others = edges.copy(); others[0, 1] = 0
assert (others == 0).all(), edges
assert int(ove["exchange"]) == int(edges.sum()), ove
assert np.isfinite(float(lse))
print("EX-STARVED-EDGE", edges.tolist())

# ---- non-divisible window: a 3-tile strip over a 2-wide "part" axis is
# PADDED (ceil sub-windows, masked pad tiles) and the loss still equals
# the all-gather loss at 1e-6 — for scalar and matrix budgets ----
bad = TileGrid(24, 8, 8, 8)          # 3 tiles, part axis 2
Tb = bad.n_tiles
cams_b = orbital_rig(V, (0.5, 0.5, 0.5), 1.6, width=24, height=8)
cb_dev = jax.device_put(select(cams_b, jnp.arange(V)), b_sh["cam"])
gtb = jax.device_put(
    jnp.zeros((V, Pn * Tb, 3, bad.tile_h, bad.tile_w)), b_sh["gt_tiles"])
mkb = jax.device_put(
    jnp.ones((V, Pn * Tb, bad.tile_h, bad.tile_w), bool),
    b_sh["mask_tiles"])
esb = ExchangeSchedule()
Bb = probe_gs_exchange(esb, mesh2d, bad, g_dev, cb_dev, views=V,
                       per_edge=True)
fgb = make_gs_forward(mesh2d, bad, K=K, impl="ref", views=V,
                      return_overflow=True)
lgb, _ = jax.jit(fgb)(g_dev, cb_dev, gtb, mkb)
for eb in (None, Bb):                # scalar (unbudgeted) and matrix
    feb = make_gs_forward(mesh2d, bad, K=K, impl="ref", views=V,
                          return_overflow=True, exchange=True,
                          exchange_budget=eb)
    leb, oeb = jax.jit(feb)(g_dev, cb_dev, gtb, mkb)
    assert int(oeb["exchange"]) == 0, oeb
    np.testing.assert_allclose(float(leb), float(lgb),
                               rtol=1e-6, atol=1e-7)
print("EX-PAD-MATCH", float(lgb))

# ---- loud validation: return_tiles cannot reassemble padded sub-windows;
# the strip prefilter composed under exchange still refuses to build ----
try:
    make_gs_forward(mesh2d, bad, K=K, views=V, exchange=True,
                    return_tiles=True)
    raise SystemExit("padded return_tiles not enforced")
except ValueError as e:
    assert "divide" in str(e), e
try:
    make_gs_forward(mesh2d, grid, K=K, views=V, exchange=True,
                    strip_budget=0.5)
    raise SystemExit("strip_budget not enforced")
except ValueError as e:
    assert "strip_budget" in str(e), e
print("EX-VALIDATE")
"""


@pytest.mark.slow
def test_sparse_exchange_matches_all_gather():
    """The sparse-overlap exchange on 4 forced host devices: probed edge
    budgets (scalar AND per-edge matrix), forward tiles/loss == the
    all-gather forward at 1e-6 (dense and tiered, 2-D ("part", "view")
    and 1-D ("part",) meshes, overflow 0, in-step demand == the host
    probe), train-step params == the all-gather step at 1e-6 (dense and
    tiered+sorted), a starved budget fires the psum'd counter — only on
    the starved edge for matrices — with well-formed (finite) outputs, a
    non-divisible window pads instead of refusing (loss parity held), and
    invalid configs are rejected loudly."""
    code = EXCHANGE_SCRIPT % {"src": SRC}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-3000:])
    for tok in ("EX-PROBE", "EX-PROBE-EDGES", "EX-FWD-MATCH", "EX-1D-MATCH",
                "EX-TAU-MATCH", "EX-STEP-MATCH", "EX-STARVED",
                "EX-STARVED-EDGE", "EX-PAD-MATCH", "EX-VALIDATE"):
        assert tok in out.stdout, tok


EXDRIVER_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys, json, glob, tempfile
sys.path.insert(0, r"%(src)s")
import jax, jax.numpy as jnp
import numpy as np

from repro.core.cameras import orbital_rig
import repro.core.distributed as dist
from repro.core.distributed import fit_partitions, rebalance_partitions
from repro.core.gaussians import from_points
from repro.core.pipeline import render_views
from repro.core.tiling import TileGrid
from repro.core.train import GSTrainCfg, init_opt
from repro.data.isosurface import point_cloud_for
from repro.runtime import CheckpointManager
from repro.launch.mesh import make_mesh

N, res, V = 256, 32, 4
pts, cols = point_cloud_for("sphere_shell", N)
pts, cols = pts[:N], cols[:N]
# break the shell's symmetry ties: rebalance bit-stability holds for
# tie-free depth scores (the two-key top-k falls back to ROW INDEX on
# equal scores, and the permutation moves rows), so the fixture must not
# hand the tie-break a coin to flip
pts = pts + 1e-4 * np.random.default_rng(0).standard_normal(pts.shape)
cams = orbital_rig(V, (0.5, 0.5, 0.5), 1.6, width=res, height=res)
mesh = make_mesh((2, 2), ("part", "view"))
grid = TileGrid(res, res, 8, 16)
g_gt = from_points(jnp.asarray(pts), jnp.asarray(cols), opacity=0.95)
gts = jnp.asarray(render_views(g_gt, cams, grid, K=16, bg=0.0)[0])[None]
masks = jnp.ones((1, V, res, res), bool)
g0 = from_points(jnp.asarray(pts), jnp.asarray(cols), capacity=N + 128,
                 opacity=0.7)
g_b = jax.tree.map(lambda x: x[None], g0)           # (P=1, N) batched

def run(cfgx, **kw):
    base = dict(mesh=mesh, steps=4, extent=1.0, grid=grid,
                key=jax.random.PRNGKey(1))
    base.update(kw)
    return fit_partitions(g_b, cams, gts, masks, cfgx, **base)

# ---- full tiered lifecycle (probe -> train -> densify -> re-probe)
# parity: the exchange trajectory equals the all-gather trajectory at
# 1e-6, losses AND trainables, through a densify event ----
kwl = dict(steps=6, densify_every=3, densify_from=0)
cfg_t = GSTrainCfg(K=16, lambda_dssim=0.0, bg=0.0, view_batch=2,
                   lr_colors=5e-2, max_new=64, densify_grad_thresh=1e-9)
cfg_te = GSTrainCfg(K=16, lambda_dssim=0.0, bg=0.0, view_batch=2,
                    lr_colors=5e-2, max_new=64, densify_grad_thresh=1e-9,
                    exchange=True)
gg, _, lg = run(cfg_t, **kwl)
ge, _, le = run(cfg_te, **kwl)
np.testing.assert_allclose(le, lg, rtol=1e-5, atol=1e-6)
for k, v in gg.trainable().items():
    np.testing.assert_allclose(np.asarray(getattr(ge, k)), np.asarray(v),
                               rtol=1e-6, atol=1e-6, err_msg=k)
print("EXD-PARITY", [round(l, 5) for l in le])

# ---- rebalance_partitions unit invariants on a skewed population ----
g_skew = jax.device_get(g_b)
cap = g_skew.means.shape[1]
act = np.zeros((1, cap), bool)
act[0, : cap // 2] = True          # every live splat on shard 0
g_skew = g_skew._replace(active=jnp.asarray(act))
opt0 = init_opt(g_skew)
g_r, o_r, moved = rebalance_partitions(g_skew, opt0, mesh, threshold=1.5)
assert moved
act_r = np.asarray(g_r.active)
live = act_r.reshape(1, 2, cap // 2).sum(-1)
assert abs(int(live[0, 0]) - int(live[0, 1])) <= 1, live
# a pure permutation: the live rows' parameters are preserved as a set
want = np.sort(np.asarray(g_skew.means)[np.asarray(g_skew.active)], axis=0)
got = np.sort(np.asarray(g_r.means)[act_r], axis=0)
np.testing.assert_array_equal(got, want)
# under-threshold skew is left untouched
_, _, moved2 = rebalance_partitions(g_r, opt0, mesh, threshold=1.5)
assert not moved2
print("EXD-REBALANCE-UNIT")

# ---- rebalance leaves the loss trajectory BIT-stable: with tie-free
# scores the two-key top-k is row-order independent, so forced
# permutations (threshold=0) must not move a single float ----
cfg_x = GSTrainCfg(K=16, dense_k=16, lambda_dssim=0.0, bg=0.0,
                   view_batch=2, lr_colors=5e-2, exchange=True)
_, _, l_plain = run(cfg_x)
_, _, l_reb = run(cfg_x, rebalance_every=2, rebalance_threshold=0.0)
np.testing.assert_array_equal(np.asarray(l_plain), np.asarray(l_reb))
print("EXD-REBALANCE-STABLE", [round(l, 5) for l in l_reb])

# ---- starved pinned budget: the psum'd counter feeds geometric growth
# (checkpointed budget ends > 1) and every loss stays finite ----
ck_g = CheckpointManager(tempfile.mkdtemp(), keep=0)
cfg_s = GSTrainCfg(K=16, dense_k=16, lambda_dssim=0.0, bg=0.0,
                   view_batch=2, lr_colors=5e-2, exchange=True,
                   exchange_budget=1)
_, _, l_s = run(cfg_s, steps=3, ckpt=ck_g, ckpt_every=3)
assert np.isfinite(l_s).all(), l_s
man = sorted(glob.glob(os.path.join(ck_g.root, "step_*", "manifest.json")))
state = json.load(open(man[-1]))["extra"]["exchange"]
assert state["budget"] > 1, state
print("EXD-GROWTH", state["budget"])

# ---- checkpoint resume restores the probed budget WITHOUT re-probing:
# with the probe monkeypatched to explode, the resumed run still matches
# the uninterrupted trajectory ----
cfg_r = GSTrainCfg(K=16, dense_k=16, lambda_dssim=0.0, bg=0.0,
                   view_batch=2, lr_colors=5e-2, exchange=True)
_, _, l_full = run(cfg_r, steps=6)
ck_r = CheckpointManager(tempfile.mkdtemp(), keep=0)
run(cfg_r, steps=4, ckpt=ck_r, ckpt_every=4)
def boom(*a, **k):
    raise AssertionError("probe_gs_exchange called on resume")
dist.probe_gs_exchange = boom
_, _, l_resumed = run(cfg_r, steps=6, ckpt=ck_r, ckpt_every=4)
assert len(l_resumed) == 2, l_resumed
np.testing.assert_allclose(l_resumed, l_full[4:], rtol=1e-6, atol=1e-7)
print("EXD-RESUME-NOREPROBE", [round(l, 5) for l in l_resumed])
"""


BF16_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, r"%(src)s")
import jax, jax.numpy as jnp
import numpy as np

from repro.core.cameras import orbital_rig, select
from repro.core.distributed import (ExchangeSchedule, gs_shardings,
                                    make_gs_train_step, probe_gs_exchange)
from repro.core.gaussians import from_points
from repro.core.tiling import TileGrid
from repro.core.train import GSTrainCfg, GSOptState
from repro.data.isosurface import point_cloud_for
from repro.launch.mesh import make_mesh

Pn, N, res, K, V = 2, 256, 32, 16, 2
grid = TileGrid(res, res, 8, 16)
T = grid.n_tiles
pts, cols = point_cloud_for("sphere_shell", 2 * N)
pts, cols = pts[: 2 * N], cols[: 2 * N]
cams = orbital_rig(V, (0.5, 0.5, 0.5), 1.6, width=res, height=res)
cam_b = select(cams, jnp.arange(V))
g_all = from_points(jnp.asarray(pts), jnp.asarray(cols), opacity=0.8)
part = lambda i: jax.tree.map(lambda x: x[i * N:(i + 1) * N], g_all)
g_b = jax.tree.map(lambda *xs: jnp.stack(xs), part(0), part(1))
mesh2d = make_mesh((2, 2), ("part", "view"))
mesh1d = make_mesh((2,), ("part",))
gt = jnp.zeros((V, Pn * T, 3, grid.tile_h, grid.tile_w))
mask = jnp.ones((V, Pn * T, grid.tile_h, grid.tile_w), bool)
TR = ("means", "log_scales", "quats", "opacity_logit", "colors")

def one(mesh, cfgx, kt):
    step = make_gs_train_step(mesh, cfgx, grid, extent=1.0, impl="ref",
                              views=V, k_tiers=kt)
    gsh, osh, bsh = gs_shardings(mesh, views=V)
    tr = {k: getattr(g_b, k) for k in TR}
    opt = GSOptState(
        m=jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), tr),
        v=jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), tr),
        step=jnp.int32(0),
        grad_accum=jnp.zeros((Pn, N)), grad_count=jnp.zeros((Pn, N)))
    batch = {"gt_tiles": jax.device_put(gt, bsh["gt_tiles"]),
             "mask_tiles": jax.device_put(mask, bsh["mask_tiles"]),
             "cam": jax.device_put(cam_b, bsh["cam"])}
    gd, od = jax.device_put(g_b, gsh), jax.device_put(opt, osh)
    if cfgx.grad_compress == "none":
        g1, _, l = step(gd, od, batch)[:3]
        err = None
    else:
        # compressed steps share one (g, opt, err, batch) signature;
        # the stateless "bf16" mode carries err=None through it
        e0 = None if cfgx.grad_compress == "bf16" else \
            jax.device_put(jax.tree.map(
                lambda x: jnp.zeros_like(x, jnp.float32), tr), osh.m)
        g1, _, err, l = step(gd, od, e0, batch)[:4]
    return ({k: np.asarray(x) for k, x in g1.trainable().items()},
            float(l), err)

cfg32 = GSTrainCfg(K=K, lr_colors=5e-2)
cfgbf = GSTrainCfg(K=K, lr_colors=5e-2, dtype_policy="bf16")

# ---- sharding stays an execution strategy PER DTYPE: the bf16-policy step
# on the 2-D ("part", "view") mesh equals the 1-D ("part",) mesh step
# bit-for-bit (both cast the same f32 rows to bf16 BEFORE the collective
# and promote the same assignment geometry after, so every device composits
# identically rounded tables; measured diff: exactly 0.0) ----
for kt in (None, (4, 8, K)):
    p2, l2, _ = one(mesh2d, cfgbf, kt)
    p1, l1, _ = one(mesh1d, cfgbf, kt)
    for k in p2:
        np.testing.assert_allclose(p2[k], p1[k], rtol=1e-6, atol=1e-6,
                                   err_msg=f"bf16 mesh parity {k} kt={kt}")
    np.testing.assert_allclose(l2, l1, rtol=1e-6, atol=1e-7)
print("BF16-MESH-PARITY")

# ---- policy cost vs the f32 step, measured and bounded: the first Adam
# update has |delta| <= lr exactly (moment bias correction cancels), so any
# two policies differ by <= 2 lr per group; the loss gap is bf16 input
# rounding through the compositor (measured 2.7e-3 relative; asserted 1e-2).
# Spatial params see the smallest gap (measured means <= 3.2e-4) ----
p32, l32, _ = one(mesh2d, cfg32, None)
pbf, lbf, _ = one(mesh2d, cfgbf, None)
assert abs(lbf - l32) / l32 <= 1e-2, (lbf, l32)
for k in p32:
    d = np.abs(pbf[k] - p32[k]).max()
    assert d <= 0.1 + 1e-6, (k, d)      # 2 * max group lr (5e-2)
    assert np.isfinite(pbf[k]).all(), k
assert np.abs(pbf["means"] - p32["means"]).max() <= 1e-3
print("BF16-POLICY-COST")

# ---- exchange == gather WITHIN the bf16 policy: both paths move the same
# bf16-rounded rows (cast happens before either collective) and score
# overlap/assignment on the same promoted f32 geometry, so the sparse
# exchange still matches its own all-gather at the f32 suite's 1e-6 ----
es = ExchangeSchedule()
g_sh2, _, b_sh2 = gs_shardings(mesh2d, views=V)
E = probe_gs_exchange(es, mesh2d, grid, jax.device_put(g_b, g_sh2),
                      jax.device_put(cam_b, b_sh2["cam"]), views=V)
for kt in (None, (4, 8, K)):
    pg, lg, _ = one(mesh2d, cfgbf, kt)
    pe, le, _ = one(mesh2d, GSTrainCfg(K=K, lr_colors=5e-2,
                                       dtype_policy="bf16", exchange=True,
                                       exchange_budget=E), kt)
    for k in pg:
        np.testing.assert_allclose(pe[k], pg[k], rtol=1e-6, atol=1e-6,
                                   err_msg=f"bf16 exchange {k} kt={kt}")
    np.testing.assert_allclose(le, lg, rtol=1e-6, atol=1e-7)
print("BF16-EX-MATCH", E)

# ---- grad_compress through the distributed step: "bf16" wire rounding
# leaves the loss IDENTICAL (compression happens after the forward) and
# params within 3e-8 of the uncompressed step (measured; gradients this
# small round to the same Adam direction); "int8" returns a finite nonzero
# error-feedback tree and params within the 2 lr first-step envelope ----
pc, lc, _ = one(mesh2d, GSTrainCfg(K=K, lr_colors=5e-2,
                                   grad_compress="bf16"), None)
np.testing.assert_allclose(lc, l32, rtol=0, atol=1e-7)
for k in p32:
    np.testing.assert_allclose(pc[k], p32[k], rtol=1e-6, atol=1e-6, err_msg=k)
pi, li, err = one(mesh2d, GSTrainCfg(K=K, lr_colors=5e-2,
                                     grad_compress="int8"), None)
np.testing.assert_allclose(li, l32, rtol=0, atol=1e-7)
leaves = jax.tree.leaves(err)
assert leaves and all(np.isfinite(np.asarray(e)).all() for e in leaves)
assert max(float(jnp.abs(e).max()) for e in leaves) > 0.0
for k in p32:
    assert np.abs(pi[k] - p32[k]).max() <= 0.1 + 1e-6, k
print("BF16-COMPRESS")
"""


@pytest.mark.slow
@pytest.mark.dtype
def test_bf16_policy_distributed_step():
    """dtype_policy="bf16" through the distributed train step on 4 forced
    host devices: 2-D mesh == 1-D mesh bit-for-bit (sharding stays an
    execution strategy per dtype), the policy cost vs the f32 step is
    bounded and documented, the sparse exchange still equals the all-gather
    at 1e-6 WITHIN the policy, and both grad_compress wire modes keep the
    step's loss/params inside their measured envelopes."""
    code = BF16_SCRIPT % {"src": SRC}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-3000:])
    for tok in ("BF16-MESH-PARITY", "BF16-POLICY-COST", "BF16-EX-MATCH",
                "BF16-COMPRESS"):
        assert tok in out.stdout, tok


@pytest.mark.slow
def test_exchange_driver_lifecycle():
    """fit_partitions under cfg.exchange on the 4-device 2-D mesh: the full
    tiered probe/densify/re-probe trajectory equals the all-gather driver
    at 1e-6; rebalance_partitions deals live rows evenly (pure permutation)
    and a forced rebalance leaves the loss trajectory bit-identical; a
    starved pinned budget grows geometrically off the psum'd counter
    (visible in the checkpointed state) with finite losses throughout; and
    a checkpoint resume restores the probed budget without calling the
    probe again."""
    code = EXDRIVER_SCRIPT % {"src": SRC}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-3000:])
    for tok in ("EXD-PARITY", "EXD-REBALANCE-UNIT",
                "EXD-REBALANCE-STABLE", "EXD-GROWTH",
                "EXD-RESUME-NOREPROBE"):
        assert tok in out.stdout, tok

"""Table IV — multi-node scaling (the paper's headline: 3.1x at 8 nodes).

Nodes = independent spatial partitions (paper §II): the wall-clock of an
n-node run is the MAX over per-partition training times (they run
concurrently on the cluster; we train them sequentially on CPU and report
the max, plus the sum for reference).  Work per node shrinks ~1/n in
gaussians — the paper's speedup mechanism — while fixed per-step costs
(camera, pixel pipeline) bound the curve exactly as the paper observes for
the smaller Rayleigh–Taylor dataset at 8 nodes.

A second, MESH-SHAPE axis sweeps the distributed shard_map step itself
(docs/distributed-training.md): for each ("part"=p, "view"=v) shape a
subprocess forces p*v host CPU devices and times the tiered 2-D-mesh train
step — per-step wall-clock, not quality.  CPU numbers only sanity-check
the collective schedule (host "devices" share the same cores, so don't
expect speedups; see ROADMAP); the same harness pointed at a real pod
slice is the true Table IV reproduction.  Enable with
``--mesh-shapes 1x1,2x1,2x2`` (or mesh_shapes=...; full runs default to a
small sweep).
"""

from __future__ import annotations

import argparse
import os
import re
import subprocess
import sys

from benchmarks.common import fmt_minutes, parallel_time, save_result
from repro.core.pipeline import PipelineCfg, run_pipeline
from repro.core.train import GSTrainCfg

_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=%(dev)d "
                           + os.environ.get("XLA_FLAGS", ""))
import time
import jax, jax.numpy as jnp
from repro.core.cameras import orbital_rig, select
from repro.core.distributed import gs_shardings, make_gs_train_step
from repro.core.gaussians import from_points
from repro.core.tiling import TileGrid
from repro.core.train import GSTrainCfg, GSOptState
from repro.data.isosurface import point_cloud_for
from repro.launch.mesh import make_mesh

p, v = %(p)d, %(v)d
Pn, N, res, V, steps = 1, %(n)d, %(res)d, %(views)d, %(steps)d
grid = TileGrid(res, res, 8, 16)
pts, cols = point_cloud_for("sphere_shell", N)
g = jax.tree.map(lambda x: x[None],
                 from_points(jnp.asarray(pts), jnp.asarray(cols),
                             opacity=0.8))
N = g.means.shape[1]        # the extractor may return fewer than requested
cams = orbital_rig(V, (0.5, 0.5, 0.5), 1.6, width=res, height=res)
cam_b = select(cams, jnp.arange(V))
gt = jnp.full((V, Pn * grid.n_tiles, 3, grid.tile_h, grid.tile_w), 0.5)
mask = jnp.ones((V, Pn * grid.n_tiles, grid.tile_h, grid.tile_w), bool)

mesh = make_mesh((p, v), ("part", "view"))
cfg = GSTrainCfg(K=32)                      # tiered by default
g_sh, opt_sh, b_sh = gs_shardings(mesh, views=V)
# production shape: probe measured tier caps first (the tier_caps=None
# fallback is always-exact but strip-sized — not what a real run pays).
# probe_gs_schedule is the driver's shared in-mesh probe: occupancy over
# each device's folded (Vl*T,) binning domain, pmax-reduced so every host
# lands on the same cap ladder (it replaced this benchmark's old ad-hoc
# host-side occupancy reshape).
from repro.core.distributed import probe_gs_schedule
sched = cfg.tier_schedule()
probe_gs_schedule(sched, mesh, grid, jax.device_put(g, g_sh),
                  jax.device_put(cam_b, b_sh["cam"]), views=V)
step = make_gs_train_step(mesh, cfg, grid, extent=1.0, impl="ref", views=V,
                          k_tiers=sched.k_tiers, tier_caps=sched.tier_caps)
tr = g.trainable()
opt = GSOptState(
    m=jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), tr),
    v=jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), tr),
    step=jnp.int32(0),
    grad_accum=jnp.zeros((Pn, N)), grad_count=jnp.zeros((Pn, N)))
batch = {"gt_tiles": jax.device_put(gt, b_sh["gt_tiles"]),
         "mask_tiles": jax.device_put(mask, b_sh["mask_tiles"]),
         "cam": jax.device_put(cam_b, b_sh["cam"])}
gd, od = jax.device_put(g, g_sh), jax.device_put(opt, opt_sh)
gd, od, l = step(gd, od, batch)             # compile + warm
jax.block_until_ready(l)
t0 = time.perf_counter()
for _ in range(steps):
    gd, od, l = step(gd, od, batch)
jax.block_until_ready(l)
dt = (time.perf_counter() - t0) / steps
print(f"MESHRESULT part={p} view={v} step_ms={dt * 1e3:.1f} "
      f"loss={float(l):.5f}")
"""


def run_mesh_sweep(shapes, *, n=4096, res=64, views=4, steps=5):
    """Time the tiered ("part", "view") train step per mesh shape.

    shapes: iterable of (part, view) ints.  Each shape runs in its own
    subprocess (XLA's host-device count is fixed at import time).  Returns
    {(p, v): step_ms}.
    """
    src = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "src")
    out = {}
    for p, v in shapes:
        code = _MESH_SCRIPT % dict(dev=p * v, p=p, v=v, n=n, res=res,
                                   views=views, steps=steps)
        env = dict(os.environ)
        env["PYTHONPATH"] = src + (
            os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
        try:
            proc = subprocess.run([sys.executable, "-c", code], env=env,
                                  capture_output=True, text=True,
                                  timeout=1200)
        except subprocess.TimeoutExpired:
            print(f"[table4] mesh {p}x{v} FAILED: timed out after 1200s")
            continue
        m = re.search(r"MESHRESULT part=\d+ view=\d+ step_ms=([\d.]+)",
                      proc.stdout)
        if proc.returncode != 0 or not m:
            print(f"[table4] mesh {p}x{v} FAILED:\n{proc.stderr[-1500:]}")
            continue
        out[(p, v)] = float(m.group(1))
    if out:
        print("\n[table4] mesh-shape sweep — tiered ('part', 'view') step "
              f"({n} splats, {views} views @ {res}^2, host CPU devices)")
        print(f"{'mesh':>8s} {'devices':>8s} {'step_ms':>9s}")
        for (p, v), ms in out.items():
            print(f"{p:>4d}x{v:<3d} {p * v:8d} {ms:9.1f}")
        save_result("table4_mesh_sweep",
                    {f"{p}x{v}": ms for (p, v), ms in out.items()})
    return out


def run(datasets=("rayleigh_taylor", "richtmyer_meshkov"),
        nodes=(2, 4, 8), steps=60, resolution=48, views=8, quick=False,
        mesh_shapes=None):
    if quick:
        steps, views, nodes = 30, 6, (2, 4, 8)
        datasets = ("rayleigh_taylor",)
    if mesh_shapes is None and not quick:
        mesh_shapes = ((1, 1), (2, 1), (2, 2))
    results = {}
    for ds in datasets:
        for n in nodes:
            res = run_pipeline(PipelineCfg(
                dataset=ds, tier="scale", n_parts=n, resolution=resolution,
                steps=steps, n_views=views, train=GSTrainCfg()))
            results[(ds, n)] = dict(
                wall=parallel_time(res.train_seconds),
                total=sum(res.train_seconds),
                psnr=res.psnr, ssim=res.ssim,
                n_gaussians=res.n_gaussians)

    print("\n[table4] multi-node scaling — wall = max over partitions "
          f"({steps} steps @ {resolution}^2, CPU tier; paper Table IV)")
    print(f"{'dataset':20s} {'nodes':>5s} {'wall':>9s} {'speedup':>8s} "
          f"{'PSNR':>7s} {'SSIM':>7s}")
    for ds in datasets:
        base = None
        for n in nodes:
            if (ds, n) not in results:
                continue
            r = results[(ds, n)]
            base = base or r["wall"] * nodes[0]  # normalise vs smallest run
            speed = results[(ds, nodes[0])]["wall"] / r["wall"]
            print(f"{ds:20s} {n:5d} {fmt_minutes(r['wall']):>9s} "
                  f"{speed:7.2f}x {r['psnr']:7.2f} {r['ssim']:7.4f}")
    save_result("table4_multinode", {
        f"{k[0]}|{k[1]}": v for k, v in results.items()})
    if mesh_shapes:
        run_mesh_sweep(mesh_shapes)
    return results


def _parse_shapes(spec: str):
    """"2x1,2x2" -> ((2, 1), (2, 2))."""
    shapes = []
    for part in spec.split(","):
        p, v = part.lower().split("x")
        shapes.append((int(p), int(v)))
    return tuple(shapes)


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--mesh-shapes", default=None,
                    help="comma list of PARTxVIEW mesh shapes to sweep the "
                         "distributed step over, e.g. 1x1,2x1,2x2 "
                         "(quick runs skip the sweep unless given)")
    ap.add_argument("--mesh-only", action="store_true",
                    help="run only the mesh-shape sweep")
    a = ap.parse_args()
    shapes = _parse_shapes(a.mesh_shapes) if a.mesh_shapes else None
    if a.mesh_only:
        run_mesh_sweep(shapes or ((1, 1), (2, 1), (2, 2)))
    else:
        run(quick=a.quick, mesh_shapes=shapes)

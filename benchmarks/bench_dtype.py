"""Mixed-precision dtype policy: payload + wall-clock at f32 vs bf16, and
the int8 cold-attribute checkpoint size (PR 8 tentpole gate).

Under ``dtype_policy="bf16"`` (core.dtypes) the gathered/exchanged splat
tables move over the collectives in bf16 — every lane of every row halves,
so the per-device communicated payload is EXACTLY half the f32 policy's
(asserted, not just reported).  Compositing still accumulates f32, so the
policy is a storage/wire dtype, not a math change — which is why parity
can be asserted before anything is timed:

  * WITHIN the bf16 policy the sparse exchange must still equal the
    all-gather at 1e-6 (both move identically rounded rows);
  * ACROSS policies the loss gap is bf16 input rounding through the
    compositor, bounded at 5e-2 relative (the distributed test suite pins
    the same band).

Wall-clock is reported for context only: on forced HOST devices the
collectives are memcpy-emulated, so payload bytes — not step time — is the
headline number (same caveat as bench_exchange).

The int8 checkpoint leg quantizes the scene's cold attributes (SH color +
opacity logit, runtime.checkpoint.quantize_cold) and measures real bytes
on disk vs the f32 checkpoint — the size must actually shrink.

Runs its measurement in a SUBPROCESS with
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` (the flag must be
set before jax initializes), mesh ("part",) x 4.

    PYTHONPATH=src python -m benchmarks.bench_dtype [--smoke]
        [--res 128] [--points-per-part 512] [--reps 3]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from benchmarks.common import save_result

N_DEV = 4


def _inner(*, res: int, n_local: int, views: int, reps: int):
    """Runs inside the forced-host-device subprocess; prints one RESULT
    line of JSON as its last stdout line."""
    import tempfile
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.cameras import orbital_rig, select
    from repro.core.distributed import (ExchangeSchedule, gs_shardings,
                                        make_gs_exchange_probe,
                                        make_gs_forward, make_gs_train_step)
    from repro.core.gaussians import from_points
    from repro.core.projection import project
    from repro.core.tiling import TileGrid, splat_features
    from repro.core.train import GSOptState, GSTrainCfg
    from repro.data.isosurface import point_cloud_for
    from repro.launch.mesh import make_mesh
    from repro.runtime.checkpoint import CheckpointManager, quantize_cold

    K = 16
    n_total = N_DEV * n_local
    grid = TileGrid(res, res, 8, 16)
    pts, cols = point_cloud_for("kingsnake", int(n_total * 1.5))
    pts, cols = pts[:n_total], cols[:n_total]
    cams = orbital_rig(views, (0.5, 0.5, 0.5), 0.8, width=res, height=res)
    cam_b = select(cams, jnp.arange(views))
    g_all = from_points(jnp.asarray(pts), jnp.asarray(cols),
                        init_scale=0.008 if res >= 128 else 0.01,
                        opacity=0.8)
    g_b = jax.tree.map(lambda x: x[None], g_all)       # (P=1, N, ...)

    mesh = make_mesh((N_DEV,), ("part",))
    g_sh, opt_sh, b_sh = gs_shardings(mesh, views=views)
    g_dev = jax.device_put(g_b, g_sh)
    cam_dev = jax.device_put(cam_b, b_sh["cam"])

    # ---- payload accounting: the gathered table is rows x (F + 3) lanes;
    # the wire dtype is the whole story, so bf16 is EXACTLY half ----
    F = splat_features(project(g_all, select(cams, 0))).shape[-1]
    rows = N_DEV * views * n_local
    payload_f32 = rows * (F + 3) * 4
    payload_bf16 = rows * (F + 3) * 2
    assert payload_bf16 * 2 == payload_f32

    gt = jnp.zeros((views, grid.n_tiles, 3, grid.tile_h, grid.tile_w))
    mask = jnp.ones((views, grid.n_tiles, grid.tile_h, grid.tile_w), bool)
    gt_dev = jax.device_put(gt, b_sh["gt_tiles"])
    mask_dev = jax.device_put(mask, b_sh["mask_tiles"])
    batch = {"gt_tiles": gt_dev, "mask_tiles": mask_dev, "cam": cam_dev}

    # ---- parity BEFORE timing #1: within the bf16 policy the exchange
    # forward equals the all-gather forward at 1e-6 ----
    max_edge = int(jax.jit(make_gs_exchange_probe(mesh, grid, views=views))(
        g_dev, cam_dev))
    E = ExchangeSchedule().probe_budget(max_edge, n_local)
    l_pair = []
    for exch in (False, True):
        f = make_gs_forward(mesh, grid, K=K, impl="ref", views=views,
                            dtype_policy="bf16", exchange=exch,
                            exchange_budget=E if exch else None)
        l_pair.append(float(jax.jit(f)(g_dev, cam_dev, gt_dev, mask_dev)))
    np.testing.assert_allclose(l_pair[1], l_pair[0], rtol=1e-6, atol=1e-7)

    def fresh_state():
        g = jax.tree.map(jnp.array, g_b)
        tr = {k: getattr(g, k) for k in
              ("means", "log_scales", "quats", "opacity_logit", "colors")}
        o = GSOptState(
            m=jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), tr),
            v=jax.tree.map(lambda x: jnp.zeros_like(x, jnp.float32), tr),
            step=jnp.int32(0),
            grad_accum=jnp.zeros((1, n_total)),
            grad_count=jnp.zeros((1, n_total)))
        return jax.device_put(g, g_sh), jax.device_put(o, opt_sh)

    def timed(cfg):
        step = make_gs_train_step(mesh, cfg, grid, extent=1.0, impl="ref",
                                  views=views)
        g, o = fresh_state()
        g, o, loss = step(g, o, batch)                 # warmup: compile
        loss = float(jax.block_until_ready(loss))
        best = float("inf")
        for _ in range(reps):
            t0 = time.perf_counter()
            g, o, l = step(g, o, batch)
            jax.block_until_ready(l)
            best = min(best, time.perf_counter() - t0)
        return best, loss

    t32, l32 = timed(GSTrainCfg(K=K))
    tbf, lbf = timed(GSTrainCfg(K=K, dtype_policy="bf16"))
    # parity BEFORE reporting #2: the cross-policy loss gap stays in the
    # documented bf16 rounding band
    assert abs(lbf - l32) <= 5e-2 * abs(l32) + 1e-6, (lbf, l32)

    # ---- int8 cold-attribute checkpoint: real bytes on disk ----
    def ckpt_bytes(tree, extra=None):
        with tempfile.TemporaryDirectory() as td:
            d = CheckpointManager(td).save(1, tree, extra=extra)
            return sum(os.path.getsize(os.path.join(d, f))
                       for f in os.listdir(d) if f.endswith(".npy"))

    q, meta = quantize_cold(g_all)
    ck32 = ckpt_bytes(g_all)
    ck8 = ckpt_bytes(q, extra={"quant": meta})
    assert ck8 < ck32

    print("RESULT " + json.dumps({
        "n_devices": N_DEV, "n_local": n_local, "views": views, "res": res,
        "feature_lanes": F + 3, "exchange_budget": E,
        "payload_bytes_f32": payload_f32,
        "payload_bytes_bf16": payload_bf16,
        "payload_ratio": payload_f32 / payload_bf16,
        "t_step_f32_s": t32, "t_step_bf16_s": tbf,
        "loss_f32": l32, "loss_bf16": lbf,
        "loss_rel_gap": abs(lbf - l32) / max(abs(l32), 1e-12),
        "ckpt_bytes_f32": ck32, "ckpt_bytes_int8": ck8,
        "ckpt_reduction": ck32 / ck8}))


def run(*, res: int = 128, n_local: int = 512, views: int = 4,
        reps: int = 3, quick: bool = False):
    if quick:
        res, n_local, views, reps = 64, 256, 2, 2
    cmd = [sys.executable, "-m", "benchmarks.bench_dtype", "--inner",
           "--res", str(res), "--points-per-part", str(n_local),
           "--views", str(views), "--reps", str(reps)]
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={N_DEV}",
               JAX_PLATFORMS="cpu")
    env.setdefault("PYTHONPATH", "src")
    print(f"\n[dtype] res={res} n_local={n_local} x{N_DEV} parts "
          f"V={views} (subprocess, {N_DEV} forced host devices)")
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=1800)
    sys.stdout.write(proc.stdout[: proc.stdout.rfind("RESULT ")])
    sys.stderr.write(proc.stderr[-2000:] if proc.returncode else "")
    if proc.returncode:
        raise SystemExit(f"bench_dtype inner failed ({proc.returncode})")
    r = json.loads(proc.stdout.rstrip().rsplit("RESULT ", 1)[1])

    mb = 1.0 / (1024 * 1024)
    print("  gathered-table payload: f32 "
          f"{r['payload_bytes_f32'] * mb:7.2f} MiB  bf16 "
          f"{r['payload_bytes_bf16'] * mb:7.2f} MiB  "
          f"({r['payload_ratio']:.0f}x smaller — every wire lane halves)")
    print(f"  train step: f32 {r['t_step_f32_s'] * 1e3:8.2f} ms  bf16 "
          f"{r['t_step_bf16_s'] * 1e3:8.2f} ms  (host-device collectives "
          "are memcpy-emulated — payload is the headline)")
    print(f"  loss gap f32 vs bf16: {r['loss_rel_gap']:.2e} relative "
          "(parity asserted in-process before timing)")
    print(f"  merged checkpoint: f32 {r['ckpt_bytes_f32'] * mb:6.2f} MiB  "
          f"int8-cold {r['ckpt_bytes_int8'] * mb:6.2f} MiB  "
          f"({r['ckpt_reduction']:.2f}x smaller)")
    save_result("dtype", r)
    return r


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--res", type=int, default=128)
    ap.add_argument("--points-per-part", type=int, default=512)
    ap.add_argument("--views", type=int, default=4)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--inner", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.inner:
        _inner(res=args.res, n_local=args.points_per_part,
               views=args.views, reps=args.reps)
        return
    run(res=args.res, n_local=args.points_per_part, views=args.views,
        reps=args.reps, quick=args.smoke)


if __name__ == "__main__":
    main()

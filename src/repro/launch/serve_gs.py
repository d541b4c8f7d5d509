"""GS render serving driver (CLI).

    # train + checkpoint (writes <ckpt>/merged + scene meta):
    python -m repro.launch.train --gs --smoke --host-devices 4 \
        --steps 4 --ckpt-dir /tmp/gs
    # serve it: mixed near/far camera batches, two passes (the second
    # must hit the pose-bucket cache), telemetry JSON out:
    python -m repro.launch.serve_gs --ckpt-dir /tmp/gs --views 6 \
        --passes 2 --telemetry-json /tmp/serve.json

Loads the merged checkpoint ONCE (shape-free restore — the merged capacity
is a training outcome), builds the LOD ladder, then answers camera
requests through the bounded-queue batcher (core/serving.py): each pass
submits a mixed near/far orbital rig (near views exercise rung 0, far
views the pruned rungs) and flushes.  Exit is nonzero if a repeat pass
fails to hit the cache — the serving contract this driver exists to
demonstrate.  ``--host-devices N`` forces N host CPU devices before jax
imports (module level stays jax-free), mirroring launch/train.py so CI
can serve against the same forced-device smoke checkpoint it trained.
"""

from __future__ import annotations

import argparse
import json
import os
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--ckpt-dir", default="checkpoints",
                    help="a launch/train.py --gs checkpoint tree (must "
                         "contain merged/)")
    ap.add_argument("--views", type=int, default=6,
                    help="cameras per pass (half near, half far)")
    ap.add_argument("--passes", type=int, default=2,
                    help="times to serve the SAME rig (pass >= 2 must hit "
                         "the pose-bucket cache)")
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--cache-entries", type=int, default=64)
    ap.add_argument("--near", type=float, default=1.0,
                    help="near orbit radius, in units of the training rig "
                         "radius")
    ap.add_argument("--far", type=float, default=5.0,
                    help="far orbit radius (same units) — drives LOD rung "
                         "selection")
    ap.add_argument("--impl", default="auto",
                    choices=["auto", "ref", "interpret", "pallas"])
    ap.add_argument("--full", action="store_true",
                    help="serve a --full checkpoint on a TPU with the Pallas "
                         "kernels; exits with an error off-TPU or for a "
                         "checkpoint not trained at the (8, 128) tile")
    ap.add_argument("--telemetry-json", default=None,
                    help="write the serving telemetry + per-pass stats "
                         "as JSON")
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force N host CPU devices (before jax import)")
    args = ap.parse_args(argv)
    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices} "
            + os.environ.get("XLA_FLAGS", ""))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.cameras import Camera, orbital_rig
    from repro.core.serving import GSRenderServer
    from repro.launch.device import (FULL_IMPL, FULL_TILE,
                                     enable_compile_cache, require_tpu)

    enable_compile_cache()
    if args.full:
        require_tpu("--full")
        if args.impl not in ("auto", FULL_IMPL):
            raise SystemExit(f"--full serves impl={FULL_IMPL!r}; "
                             f"--impl {args.impl} is a CPU setting")
        args.impl = FULL_IMPL
    server, extra = GSRenderServer.from_checkpoint(
        args.ckpt_dir, impl=args.impl, max_batch=args.max_batch,
        cache_entries=args.cache_entries)
    meta = extra.get("scene", {})
    tile = (server.grid.tile_h, server.grid.tile_w)
    if args.full and tile != FULL_TILE:
        raise SystemExit(f"--full serves {FULL_TILE} tiles; this checkpoint "
                         f"was trained at {tile} (train it with --full)")
    g0 = server.ladder[0]
    print(f"[serve-gs] devices={len(jax.devices())} "
          f"model={int(np.asarray(g0.active).sum()):,} live splats "
          f"grid={server.grid.width}x{server.grid.height} "
          f"ladder K={server.schedule.k_tiers} "
          f"lod rungs={[int(np.asarray(r.active).sum()) for r in server.ladder]} "
          f"dists={tuple(round(d, 3) for d in server.lod_dists)}")

    # mixed near/far rig around the checkpointed scene frame: near views
    # stay on rung 0, far views select the pruned rungs
    rig_r = float(meta.get("radius", server.radius))
    center = meta.get("center", server.center)
    res = server.grid.width
    n_near = max(1, args.views // 2)
    n_far = max(1, args.views - n_near)
    near = orbital_rig(n_near, center, rig_r * args.near,
                       width=res, height=res)
    far = orbital_rig(n_far, center, rig_r * args.far,
                      width=res, height=res)
    rig = Camera(view=jnp.concatenate([near.view, far.view]),
                 fx=jnp.concatenate([near.fx, far.fx]),
                 fy=jnp.concatenate([near.fy, far.fy]),
                 width=res, height=res)

    passes = []
    for p in range(args.passes):
        t0 = time.perf_counter()
        results = server.serve(rig)
        dt = time.perf_counter() - t0
        hits = sum(r.cache_hit for r in results)
        rungs = sorted({r.rung for r in results})
        assert all(np.isfinite(r.rgb).all() for r in results)
        print(f"[serve-gs] pass {p}: {len(results)} requests in "
              f"{dt * 1e3:.1f}ms ({len(results) / dt:.1f} req/s)  "
              f"cache hits {hits}/{len(results)}  rungs {rungs}")
        passes.append({"requests": len(results), "wall_s": dt,
                       "req_per_s": len(results) / dt, "hits": hits,
                       "rungs": rungs})

    tel = server.telemetry()
    print(f"[serve-gs] telemetry {tel}")
    if args.telemetry_json:
        with open(args.telemetry_json, "w") as f:
            json.dump({"telemetry": tel, "passes": passes,
                       "scene": meta}, f, indent=1)
        print(f"[serve-gs] telemetry -> {args.telemetry_json}")
    if args.passes >= 2 and passes[-1]["hits"] < passes[-1]["requests"]:
        raise SystemExit(
            "[serve-gs] FAIL: repeat pass hit the cache on only "
            f"{passes[-1]['hits']}/{passes[-1]['requests']} requests")
    print("[serve-gs] ok")


if __name__ == "__main__":
    main()

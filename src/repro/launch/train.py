"""Training driver (CLI).

Two modes, one runtime:

  LM:  python -m repro.launch.train --arch minicpm-2b --smoke --steps 20
  GS:  python -m repro.launch.train --gs --dataset kingsnake --parts 2 \
           --steps 200 --resolution 64

Both wire the full production substrate: mesh construction, sharded-state
init, checkpoint/restart (resumes automatically from the latest complete
checkpoint), heartbeats, retry, gradient compression (LM), and the paper's
partition pipeline (GS).  On CPU this runs reduced configs; on a pod the
same driver runs the full ones (--full).

The GS mode is the paper's end-to-end workflow on the distributed
tier-schedule driver (core/distributed.py::fit_partitions): partition (+
ghost cells) -> per-partition GT renders + coverage masks -> TIERED
distributed training of every partition in one SPMD program on the
("part", "view") mesh (probe -> train -> densify -> re-probe; TierSchedule
state checkpointed alongside params, so a restart resumes without
re-probing) -> merge -> global render + metrics.  ``--host-devices N``
forces N host-backed CPU devices (set before jax import), so the whole
multi-device lifecycle runs on a laptop or in CI:

    python -m repro.launch.train --gs --smoke --host-devices 4 --steps 6

jax is imported lazily (inside the run functions) so the flag can take
effect; keep module-level imports jax-free.
"""

from __future__ import annotations

import argparse
import math
import os
import time


def run_lm(args):
    import jax

    from repro.configs import get_smoke, get_spec
    from repro.data.tokens import SyntheticTokens
    from repro.models import (TrainCfg, init_opt_state, init_params,
                              make_train_step)
    from repro.runtime import CheckpointManager, Heartbeat, retry_step

    spec = get_smoke(args.arch) if args.smoke else get_spec(args.arch)
    cfg = TrainCfg(total_steps=args.steps, compression=args.compression,
                   schedule=spec.lr_schedule, kv_chunk=args.kv_chunk,
                   n_microbatches=args.microbatches)
    print(f"[train] arch={spec.name} params={spec.param_count():,} "
          f"policy={spec.sharding_policy}")
    params = init_params(spec, jax.random.PRNGKey(args.seed))
    opt = init_opt_state(spec, params, cfg)
    step_fn = jax.jit(make_train_step(spec, cfg))

    ckpt = CheckpointManager(args.ckpt_dir, keep=2)
    hb = Heartbeat(args.ckpt_dir, "worker0")
    (params, opt), _, latest = ckpt.restore_latest((params, opt))
    start = latest or 0
    if latest is not None:
        print(f"[train] resumed from step {start}")

    data = SyntheticTokens(vocab=spec.vocab, seq=args.seq,
                           global_batch=args.batch, seed=args.seed)
    t0 = time.perf_counter()
    for step in range(start, args.steps):
        batch = data.batch(step)
        params, opt, metrics = retry_step(step_fn, params, opt, batch)
        hb.beat(step)
        if (step + 1) % args.log_every == 0:
            dt = (time.perf_counter() - t0) / args.log_every
            t0 = time.perf_counter()
            print(f"  step {step+1:5d} loss {float(metrics['loss']):.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f} {dt*1e3:.0f}ms/step")
        if args.ckpt_every and (step + 1) % args.ckpt_every == 0:
            ckpt.save(step + 1, (params, opt), extra={"arch": spec.name})
    ckpt.save(args.steps, (params, opt), extra={"arch": spec.name})
    print("[train] done")


def gs_train_cfg(args):
    """GSTrainCfg from the CLI flags.  ``--full`` is the paper's sizes on
    the chip: it fails off-TPU and pins the (8, 128) tile + Pallas
    kernels; otherwise the CPU tile and ``--impl`` apply."""
    from repro.core.train import GSTrainCfg
    from repro.launch.device import (CPU_TILE, FULL_IMPL, FULL_TILE,
                                     require_tpu)

    if args.full:
        require_tpu("--full")
        if args.impl not in ("auto", FULL_IMPL):
            raise SystemExit(f"--full runs impl={FULL_IMPL!r}; "
                             f"--impl {args.impl} is a CPU setting")
        (th, tw), impl = FULL_TILE, FULL_IMPL
    else:
        (th, tw), impl = CPU_TILE, args.impl
    return GSTrainCfg(view_batch=args.view_batch or 1,
                      exchange=args.exchange,
                      exchange_budget=args.exchange_budget,
                      dtype_policy=args.dtype_policy,
                      grad_compress=args.grad_compress,
                      tile_h=th, tile_w=tw, impl=impl)


def gs_mesh(args, cfg, n_views: int):
    """The ("part", "view") mesh: ``--mesh PxV`` or the widest "view" axis
    the EFFECTIVE minibatch supports (the driver clamps view_batch to the
    view count), the rest going to "part"."""
    import jax

    from repro.launch.mesh import make_mesh

    n_dev = len(jax.devices())
    if args.mesh:
        p, v = (int(x) for x in args.mesh.lower().split("x"))
        if p * v != n_dev:
            raise SystemExit(f"--mesh {args.mesh} needs {p * v} devices, "
                             f"have {n_dev} (try --host-devices {p * v})")
    else:
        v = math.gcd(max(1, min(cfg.view_batch, n_views)), n_dev)
        p = n_dev // v
    return make_mesh((p, v), ("part", "view"))


def prepare_gs(args, cfg, ds, n_views: int, part_shards: int):
    """Host + device prep of one GS scene: isosurface extraction ->
    partition (+ ghost halo) -> equal-capacity (P, N) stack (capacity a
    multiple of ``part_shards``) -> per-partition GT renders + coverage
    masks.  -> SimpleNamespace of everything the trainer and eval need."""
    import types

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.cameras import orbital_rig
    from repro.core.partition import partition_points
    from repro.core.pipeline import (build_scene, coverage_masks,
                                     gt_gaussians, init_partition_gaussians,
                                     render_views)
    from repro.core.tiling import TileGrid

    points, colors, extent = build_scene(ds, args.seed)
    center = 0.5 * (points.max(0) + points.min(0))
    radius = 1.6 * extent / 2 + 1e-3
    W = H = args.resolution
    grid = TileGrid(W, H, cfg.tile_h, cfg.tile_w)
    cams = orbital_rig(n_views, center, radius, width=W, height=H)

    # partition (+ghost halo) -> equal-capacity batched (P, N) layout
    ghost_w = ds.ghost_frac * extent if not args.no_ghost else 0.0
    parts, _ = partition_points(points, colors, args.parts,
                                ghost_width=ghost_w)
    base = max(len(pd.points) for pd in parts)
    cap = int(base * ds.capacity_factor) if args.densify_every else base
    cap = -(-cap // part_shards) * part_shards  # "part"-shardable capacity
    g = jax.tree.map(lambda *xs: jnp.stack(xs),
                     *[init_partition_gaussians(pd, capacity=cap)
                       for pd in parts])

    # per-partition GT renders of own (+ghost) data and coverage masks.
    # Training GT is rendered at bg=0: the distributed tile loss compares
    # RAW premultiplied color tiles (no background composite), so a
    # white-composited target would carry a bias the prediction can never
    # produce (the driver parity tests pin the same convention); the
    # white-background renders stay eval-only.
    gts, masks = [], []
    for pd in parts:
        part_gt, part_cov = render_views(
            gt_gaussians(pd.points, pd.colors), cams, grid, K=cfg.K,
            impl=cfg.impl, bg=0.0)
        gts.append(part_gt)
        if not args.no_mask:
            masks.append(coverage_masks(part_cov))
    return types.SimpleNamespace(
        points=points, colors=colors, extent=extent, center=center,
        radius=radius, grid=grid, cams=cams, parts=parts, g=g,
        gts=jnp.asarray(np.stack(gts)),
        masks=None if args.no_mask else jnp.asarray(np.stack(masks)))


def _gs_defaults(args, ds):
    """Resolve the dataset-dependent flag defaults in place."""
    if args.resolution is None:
        args.resolution = ds.resolutions[0] if args.full else 64
    if args.views is None:
        args.views = ds.n_views


def setup_gs(args):
    """Config, mesh and prepared scene of a ``--gs`` run -> (cfg, mesh,
    scene); see ``prepare_gs``."""
    from repro.configs.gs_datasets import get_gs_dataset

    cfg = gs_train_cfg(args)
    ds = get_gs_dataset(args.dataset, "full" if args.full else "cpu")
    _gs_defaults(args, ds)
    mesh = gs_mesh(args, cfg, args.views)
    return cfg, mesh, prepare_gs(args, cfg, ds, args.views,
                                 mesh.devices.shape[0])


def run_gs(args):
    """The GS lifecycle -> {cfg, grid, cams, losses, merged, n_live,
    n_init} for callers that check the run (chip_smoke.py)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import merge as merge_mod
    from repro.core import metrics
    from repro.core.distributed import fit_partitions
    from repro.core.pipeline import gt_gaussians, render_views
    from repro.runtime import CheckpointManager

    if args.smoke:
        # tiny full-lifecycle config: 2 partitions, small scene, densify
        # mid-run so the probe -> train -> densify -> re-probe loop (and a
        # checkpointed schedule) is exercised end to end on forced host
        # devices.  --steps/--ckpt-dir stay caller-controlled so CI can run
        # the resume path with a second invocation.
        args.dataset = "sphere_shell"
        args.parts = 2
        args.resolution = min(args.resolution or 32, 32)
        args.views = args.views or 4
        args.view_batch = args.view_batch or 2
        if args.densify_every == 0:
            args.densify_every, args.densify_from = 2, 1
        if args.ckpt_every == 0:
            args.ckpt_every = 2

    cfg, mesh, sc = setup_gs(args)
    n_views = args.views
    p, v = mesh.devices.shape
    grid, cams = sc.grid, sc.cams

    kt = cfg.resolved_k_tiers()
    table = "exchange" if cfg.exchange else "all-gather"
    if cfg.exchange and cfg.exchange_budget:
        table += f"(budget={cfg.exchange_budget})"
    print(f"[train-gs] dataset={args.dataset} parts={args.parts} "
          f"res={args.resolution} views={n_views} mesh={p}x{v} "
          f"({mesh.devices.size} devices) ghost={not args.no_ghost} "
          f"mask={not args.no_mask} table={table} raster="
          f"{'tiered ' + str(kt) if kt else 'dense K=' + str(cfg.assign_K)} "
          f"tile={cfg.tile_h}x{cfg.tile_w} impl={cfg.impl} "
          f"dtype={cfg.dtype_policy} grad-compress={cfg.grad_compress}")

    ckpt = CheckpointManager(args.ckpt_dir, keep=2)
    latest = ckpt.latest_restorable_step()
    if latest is not None:
        print(f"[train-gs] resuming from checkpoint step {latest} "
              "(schedule restored, no re-probe)")
    sched = cfg.tier_schedule()
    t0 = time.perf_counter()
    g1, _, losses = fit_partitions(
        sc.g, cams, sc.gts, sc.masks, cfg, mesh=mesh, steps=args.steps,
        extent=sc.extent, key=jax.random.PRNGKey(args.seed),
        densify_every=args.densify_every, densify_from=args.densify_from,
        grid=grid, schedule=sched, impl=cfg.impl, ckpt=ckpt,
        ckpt_every=args.ckpt_every, rebalance_every=args.rebalance_every,
        log_every=args.log_every)
    train_s = time.perf_counter() - t0
    # a restored checkpoint may already be PAST --steps; label everything
    # downstream (log line, per-partition checkpoints) with the step the
    # parameters actually correspond to
    done = max(args.steps, latest or 0)
    if losses:
        print(f"[train-gs] trained steps {latest or 0}->{done} "
              f"({len(losses)} ran, {train_s:.1f}s)  "
              f"final loss {losses[-1]:.4f}")
    else:
        print(f"[train-gs] checkpoint already at step {done}; "
              "skipping to merge")
    if sched is not None:
        print(f"[train-gs] schedule: {sched}")

    # per-partition checkpoints (paper's O(1/n) failure recovery), then the
    # global reconstruction: merge -> render -> metrics
    host = jax.device_get(g1)
    part_list = [jax.tree.map(lambda x: x[i], host)
                 for i in range(args.parts)]
    pckpt = CheckpointManager(os.path.join(args.ckpt_dir, "partitions"),
                              keep=2)
    for pid, gp in enumerate(part_list):
        pckpt.save(done, gp, partition=pid,
                   extra={"dataset": args.dataset})

    merged = merge_mod.merge_partitions(part_list,
                                        [pd.part_id for pd in sc.parts])
    gt_imgs, _ = render_views(gt_gaussians(sc.points, sc.colors), cams,
                              grid, K=cfg.K, impl=cfg.impl)
    renders, _ = render_views(merged, cams, grid, K=cfg.K, impl=cfg.impl)
    ps = float(np.mean([metrics.psnr(jnp.asarray(renders[i]),
                                     jnp.asarray(gt_imgs[i]))
                        for i in range(n_views)]))
    ss = float(np.mean([metrics.ssim(jnp.asarray(renders[i]),
                                     jnp.asarray(gt_imgs[i]))
                        for i in range(n_views)]))
    n_live = int(np.asarray(merged.active).sum())
    print(f"[train-gs] PSNR {ps:.2f}  SSIM {ss:.4f}  gaussians {n_live:,}")

    # train->serve handoff: the MERGED model as its own checkpoint (the
    # per-partition tree above is the recovery path; the serving driver
    # launch/serve_gs.py restores THIS one, shape-free) + the scene frame
    # it needs to rebuild the grid/rig, + the final merged render so the
    # round-trip test can pin restore-and-render == trainer output at 1e-6
    mckpt = CheckpointManager(os.path.join(args.ckpt_dir, "merged"), keep=2)
    merged_extra = {"scene": {
        "dataset": args.dataset, "resolution": args.resolution,
        "center": [float(c) for c in sc.center], "radius": float(sc.radius),
        "extent": float(sc.extent), "n_views": int(n_views),
        "K": int(cfg.K), "tile_h": int(cfg.tile_h),
        "tile_w": int(cfg.tile_w),
    }}
    merged_save = merged
    if args.ckpt_quantize == "int8":
        # cold attributes (SH color, opacity logit) as int8 with per-tensor
        # scales riding extra["quant"]; serving dequantizes on restore
        from repro.runtime.checkpoint import quantize_cold
        merged_save, quant_meta = quantize_cold(merged)
        merged_extra["quant"] = quant_meta
        print("[train-gs] merged checkpoint cold attributes quantized "
              f"(int8, fields={list(quant_meta['fields'])})")
    mckpt.save(done, merged_save, extra=merged_extra)
    np.save(os.path.join(args.ckpt_dir, "render_final.npy"), renders)
    print(f"[train-gs] merged checkpoint (step {done}) + final render "
          f"saved under {args.ckpt_dir}")
    return {"cfg": cfg, "grid": grid, "cams": cams, "losses": losses,
            "merged": merged, "n_live": n_live,
            "n_init": int(np.asarray(sc.g.active).sum())}


def run_gs_timeseries(args):
    """Time-series training loop (``--gs --timeseries``): timesteps
    t=0..T-1 of the evolving volume, each warm-started from the previous
    timestep's committed state via the resume path (restored TierSchedule
    caps + ExchangeSchedule budgets, NO init re-probe), with delta
    checkpoints between timesteps and timestep t+1's host ingest
    (extraction -> partition -> GT renders -> masks) prefetched on a
    background thread while timestep t trains on the devices.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.gs_datasets import get_gs_dataset
    from repro.core import merge as merge_mod
    from repro.core import metrics
    from repro.core.cameras import orbital_rig
    from repro.core.distributed import ExchangeSchedule, fit_partitions
    from repro.core.pipeline import (TimestepPrefetcher, build_scene,
                                     gt_gaussians, prepare_timestep,
                                     render_views)
    from repro.core.tiling import TileGrid
    from repro.core.train import init_opt
    from repro.runtime import CheckpointManager

    if args.smoke:
        args.dataset = "sphere_shell"
        args.parts = 2
        args.resolution = min(args.resolution or 32, 32)
        args.views = args.views or 4
        args.view_batch = args.view_batch or 2
        args.timesteps = min(args.timesteps, 2)
        if args.densify_every == 0:
            args.densify_every, args.densify_from = 2, 1
        if args.densify_cap is None:
            args.densify_cap = 4096

    cfg = gs_train_cfg(args)
    ds = get_gs_dataset(args.dataset, "full" if args.full else "cpu")
    _gs_defaults(args, ds)
    n_views = args.views
    T, S = args.timesteps, args.steps

    # series-fixed frame: rig, grid, capacity all come from the t=0 scene
    # so every timestep shares ONE (P, N)/(P, V, H, W) layout — the
    # warm-started state and the delta diffs both depend on it
    points, colors, extent = build_scene(ds, args.seed, t=0.0)
    center = 0.5 * (points.max(0) + points.min(0))
    radius = 1.6 * extent / 2 + 1e-3
    W = H = args.resolution
    grid = TileGrid(W, H, cfg.tile_h, cfg.tile_w)
    cams = orbital_rig(n_views, center, radius, width=W, height=H)

    mesh = gs_mesh(args, cfg, n_views)
    p, v = mesh.devices.shape
    n_dev = mesh.devices.size

    from repro.core.partition import partition_points
    parts0, _ = partition_points(
        points, colors, args.parts,
        ghost_width=ds.ghost_frac * extent if not args.no_ghost else 0.0)
    base = max(len(pd.points) for pd in parts0)
    # capacity_factor slack covers both densify growth AND per-timestep
    # extraction drift (prepare_timestep fails loudly if a later timestep
    # outgrows it)
    cap = int(base * ds.capacity_factor) if args.densify_every else base
    cap = -(-cap // p) * p

    print(f"[train-gs-ts] dataset={args.dataset} timesteps={T} dt={args.dt} "
          f"steps/timestep={S} parts={args.parts} res={args.resolution} "
          f"mesh={p}x{v} ({n_dev} devices) capacity={cap} "
          f"densify_cap={args.densify_cap} "
          f"dtype={cfg.dtype_policy} grad-compress={cfg.grad_compress}")

    # delta-checkpoint chain: one manager, keep=0 (deltas need their whole
    # base chain on disk), full save at timestep 0, per-field sparse row
    # diffs after that.  A restart resumes at the last COMMITTED timestep.
    tck = CheckpointManager(os.path.join(args.ckpt_dir, "timeseries"),
                            keep=0)
    latest = tck.latest_restorable_step()
    t_start = 0 if latest is None else latest // S
    if t_start:
        print(f"[train-gs-ts] restarting at timestep {t_start} "
              f"(chain committed through step {latest})")

    def prep(t_idx):
        return prepare_timestep(
            ds, cams, grid, t=t_idx * args.dt, seed=args.seed,
            n_parts=args.parts, capacity=cap, K=cfg.K, impl=cfg.impl,
            use_ghost=not args.no_ghost, use_mask=not args.no_mask)

    warm = None          # (host state tree, extra, global step)
    td = None
    g1 = None
    key = jax.random.PRNGKey(args.seed)
    with TimestepPrefetcher() as pf:
        pf.submit(prep, t_start)
        for t in range(t_start, T):
            td = pf.get()
            if t + 1 < T:
                # streaming ingest: t+1's host prep overlaps t's training
                pf.submit(prep, t + 1)
            if warm is None and t > 0:
                # restart path: rebuild the warm seed from the committed
                # delta chain (exactly what a fresh process has)
                like = (jax.device_get(td.g0),
                        jax.device_get(init_opt(td.g0)))
                warm = (*tck.restore_delta(t * S, like), t * S)
            if t > 0:
                src = warm[1].get("timestep", t - 1)
                print(f"[train-gs-ts] timestep {t}: warm-start from "
                      f"timestep {src} (step {warm[2]}) — schedule + "
                      "exchange restored, no init probe")
            else:
                print("[train-gs-ts] timestep 0: cold start")

            sched = cfg.tier_schedule()
            ex = ExchangeSchedule(budget=cfg.exchange_budget) \
                if cfg.exchange else None
            t0 = time.perf_counter()
            g1, opt1, losses = fit_partitions(
                td.g0, cams, jnp.asarray(td.gts),
                None if td.masks is None else jnp.asarray(td.masks),
                cfg, mesh=mesh, steps=(t + 1) * S, extent=td.extent,
                key=key, densify_every=args.densify_every,
                # densify_from stays SERIES-absolute: the per-call key
                # fast-forward then replays exactly the densify keys a
                # continuous (or disk-resumed) run would have consumed, so
                # a repeated static timestep is bit-on the resume oracle
                densify_from=args.densify_from, grid=grid,
                schedule=sched, exchange_schedule=ex, impl=cfg.impl,
                rebalance_every=args.rebalance_every,
                log_every=args.log_every, warm_start=warm,
                densify_cap=args.densify_cap)
            dt_s = time.perf_counter() - t0
            live = int(np.asarray(g1.active).sum())
            print(f"[train-gs-ts] timestep {t} (t={td.t:.3f}): "
                  f"steps {t * S}->{(t + 1) * S} ({dt_s:.1f}s)  "
                  f"final loss {losses[-1]:.4f}  live splats {live:,}")

            # commit the timestep: full checkpoint for the chain head,
            # sparse row-delta against the previous timestep after that
            tree = jax.tree.map(jax.device_get, (g1, opt1))
            extra = {"timestep": t, "t": float(td.t),
                     "schedule": sched.state_dict() if sched else None,
                     "exchange": ex.state_dict() if ex else None,
                     "dtype_policy": cfg.dtype_policy,
                     "grad_compress": cfg.grad_compress}
            if t == 0:
                tck.save(S, tree, extra=extra)
            else:
                tck.save_delta((t + 1) * S, tree, base_step=t * S,
                               extra=extra)
            warm = (tree, extra, (t + 1) * S)

    if g1 is None:
        # the chain is already complete: reload the final timestep for the
        # merge/eval tail below
        td = prep(T - 1)
        like = (jax.device_get(td.g0), jax.device_get(init_opt(td.g0)))
        (g1, _), _ = tck.restore_delta(T * S, like)
        print(f"[train-gs-ts] chain already complete at timestep {T - 1}; "
              "skipping to merge")

    # merge + eval + serving checkpoint for the FINAL timestep (same tail
    # as the single-snapshot driver, labelled with the series step)
    done = T * S
    host = jax.device_get(g1)
    part_list = [jax.tree.map(lambda x: x[i], host)
                 for i in range(args.parts)]
    pckpt = CheckpointManager(os.path.join(args.ckpt_dir, "partitions"),
                              keep=2)
    for pid, gp in enumerate(part_list):
        pckpt.save(done, gp, partition=pid,
                   extra={"dataset": args.dataset, "timestep": T - 1})

    merged = merge_mod.merge_partitions(part_list,
                                        [pd.part_id for pd in td.parts])
    gt_imgs, _ = render_views(gt_gaussians(td.points, td.colors), cams,
                              grid, K=cfg.K, impl=cfg.impl)
    renders, _ = render_views(merged, cams, grid, K=cfg.K, impl=cfg.impl)
    ps = float(np.mean([metrics.psnr(jnp.asarray(renders[i]),
                                     jnp.asarray(gt_imgs[i]))
                        for i in range(n_views)]))
    ss = float(np.mean([metrics.ssim(jnp.asarray(renders[i]),
                                     jnp.asarray(gt_imgs[i]))
                        for i in range(n_views)]))
    print(f"[train-gs-ts] timestep {T - 1} PSNR {ps:.2f}  SSIM {ss:.4f}  "
          f"gaussians {int(np.asarray(merged.active).sum()):,}")

    mckpt = CheckpointManager(os.path.join(args.ckpt_dir, "merged"), keep=2)
    merged_extra = {"scene": {
        "dataset": args.dataset, "resolution": args.resolution,
        "center": [float(c) for c in center], "radius": float(radius),
        "extent": float(td.extent), "n_views": int(n_views),
        "K": int(cfg.K), "tile_h": int(cfg.tile_h),
        "tile_w": int(cfg.tile_w),
    }, "timestep": T - 1, "t": float(td.t)}
    merged_save = merged
    if args.ckpt_quantize == "int8":
        from repro.runtime.checkpoint import quantize_cold
        merged_save, quant_meta = quantize_cold(merged)
        merged_extra["quant"] = quant_meta
        print("[train-gs-ts] merged checkpoint cold attributes quantized "
              f"(int8, fields={list(quant_meta['fields'])})")
    mckpt.save(done, merged_save, extra=merged_extra)
    np.save(os.path.join(args.ckpt_dir, "render_final.npy"), renders)
    print(f"[train-gs-ts] merged checkpoint (step {done}) + final render "
          f"saved under {args.ckpt_dir}")


def parse_args(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--gs", action="store_true")
    # LM
    ap.add_argument("--arch", default="minicpm-2b")
    ap.add_argument("--smoke", action="store_true",
                    help="LM: reduced same-family config (CPU); GS: tiny "
                         "full-lifecycle run (2 parts, small scene, densify "
                         "+ checkpoint on)")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--kv-chunk", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--compression", default="none",
                    choices=["none", "bf16", "int8"])
    # GS
    ap.add_argument("--dataset", default="sphere_shell")
    ap.add_argument("--full", action="store_true",
                    help="the paper's sizes on a TPU: (8, 128) tiles and the "
                         "Pallas kernels; exits with an error off-TPU")
    ap.add_argument("--impl", default="auto",
                    choices=["auto", "ref", "interpret", "pallas"],
                    help="rasterizer without --full, as serve_gs --impl "
                         "(auto: pallas on TPU, ref elsewhere); the CPU "
                         "rehearsal of chip_smoke.py sets 'interpret' to "
                         "run the kernel bodies without a chip")
    ap.add_argument("--parts", type=int, default=2)
    ap.add_argument("--resolution", type=int, default=None,
                    help="image side (default: the dataset's first "
                         "resolution with --full, else 64)")
    ap.add_argument("--views", type=int, default=None)
    ap.add_argument("--view-batch", type=int, default=None,
                    help="views per minibatch step (sharded over the mesh's "
                         "'view' axis; must divide by its size)")
    ap.add_argument("--mesh", default=None,
                    help="PARTxVIEW device mesh shape, e.g. 2x2 (default: "
                         "widest 'view' axis the view batch supports)")
    ap.add_argument("--densify-every", type=int, default=0)
    ap.add_argument("--densify-from", type=int, default=100)
    ap.add_argument("--exchange", action="store_true",
                    help="sparse-overlap splat exchange instead of the "
                         "full-table all-gather (probed edge budgets, "
                         "psum'd overflow counters)")
    ap.add_argument("--exchange-budget", type=int, default=None,
                    help="pin the per-(src,dst) edge budget instead of "
                         "probing it")
    ap.add_argument("--rebalance-every", type=int, default=0,
                    help="check per-shard live-splat skew every N steps "
                         "and permute rows to rebalance (0 = off)")
    ap.add_argument("--no-ghost", action="store_true")
    ap.add_argument("--no-mask", action="store_true")
    ap.add_argument("--dtype-policy", default="f32",
                    choices=["f32", "bf16"],
                    help="GS storage/wire dtype: bf16 halves gathered/"
                         "exchanged splat tables and collective payload; "
                         "compositing, loss and optimizer stay f32. Resume "
                         "across a policy change fails loudly.")
    ap.add_argument("--grad-compress", default="none",
                    choices=["none", "bf16", "int8"],
                    help="GS gradient wire compression (optim/compress.py); "
                         "int8 carries error feedback in step state and "
                         "through checkpoints")
    ap.add_argument("--timeseries", action="store_true",
                    help="GS: train timesteps t=0..T-1 of the evolving "
                         "volume; each timestep warm-starts from the "
                         "previous one's committed state (restored "
                         "schedule/exchange, no init re-probe) with delta "
                         "checkpoints between timesteps and next-timestep "
                         "ingest prefetched during training")
    ap.add_argument("--timesteps", type=int, default=4,
                    help="number of timesteps T for --timeseries")
    ap.add_argument("--dt", type=float, default=0.1,
                    help="simulation-time spacing between timesteps "
                         "(volume fields evolve as t = index * dt)")
    ap.add_argument("--densify-cap", type=int, default=None,
                    help="hard ceiling on LIVE splats per partition: "
                         "densify stops growing at the cap, so memory "
                         "stays bounded across timesteps (GeoGaussian-"
                         "style num_max; default: uncapped)")
    ap.add_argument("--ckpt-quantize", default="none",
                    choices=["none", "int8"],
                    help="quantize merged-checkpoint cold attributes "
                         "(SH color, opacity logit) to int8 with per-tensor "
                         "scales; geometry stays f32")
    # common
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--ckpt-dir", default="checkpoints")
    ap.add_argument("--ckpt-every", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--host-devices", type=int, default=0,
                    help="force N host-backed CPU devices (applied BEFORE "
                         "jax import; lets the distributed GS driver run "
                         "its real multi-device mesh on one machine/CI)")
    return ap.parse_args(argv)


def main(argv=None):
    args = parse_args(argv)
    if args.host_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.host_devices} "
            + os.environ.get("XLA_FLAGS", ""))
    from repro.launch.device import enable_compile_cache
    enable_compile_cache()
    if args.gs and args.timeseries:
        run_gs_timeseries(args)
    elif args.gs:
        run_gs(args)
    else:
        run_lm(args)


if __name__ == "__main__":
    main()

"""Sparse-overlap splat exchange vs the full-table all-gather (tentpole
gate for the exchange path in core/distributed.py).

The all-gather moves EVERY partition's projected table to every device even
though a device's tile sub-window only needs the splats whose bboxes
overlap it.  The exchange probes a PER-EDGE (src, dst) budget matrix and
moves only the overlapping rows via a ragged ppermute ladder — so the
per-device communicated payload drops proportionally to the probed edge
overlap, not the single worst edge.  With overlap-aware (Morton-ordered)
partitioning each shard is a compact brick whose overlap concentrates on a
few screen bands, and the overlap-aware window assignment
(``window_assignment``) parks each brick's dominant band on the free local
shift — together the per-device payload DECREASES with n_part at paper
scale, the strong-scaling property this benchmark measures and (in sweep
mode) gates.  Exchange == gather loss parity at 1e-6 is asserted before
any timing, so the measured configs are known-equal.

Each measurement runs in a SUBPROCESS with
``XLA_FLAGS=--xla_force_host_platform_device_count=<n_part>`` (the flag
must be set before jax initializes, and the orchestrator has long since
imported jax), mesh ("part",) x n_part.  The TOTAL splat count is held
fixed across a sweep — scaling n_part splits the same scene finer, the
paper's strong-scaling axis.

    PYTHONPATH=src python -m benchmarks.bench_exchange [--smoke]
        [--n-part 4,8,16] [--res 256] [--points-per-part 4096] [--reps 3]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from benchmarks.common import save_result


def _inner(*, res: int, n_total: int, n_dev: int, views: int, reps: int,
           spatial_sort: bool):
    """Runs inside the forced-host-device subprocess; prints one RESULT
    line of JSON as its last stdout line."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.cameras import orbital_rig, select
    from repro.core.distributed import (ExchangeSchedule, gs_shardings,
                                        make_gs_exchange_probe,
                                        make_gs_train_step,
                                        window_assignment)
    from repro.core.gaussians import from_points
    from repro.core.partition import spatial_order
    from repro.core.projection import project
    from repro.core.tiling import TileGrid, splat_features
    from repro.core.train import GSOptState, GSTrainCfg

    K = 16
    n_local = n_total // n_dev
    grid = TileGrid(res, res, 8, 16)
    # kingsnake close-up: the surface fills the frame and spreads across
    # the horizontal tile bands, so each device's sub-window genuinely sees
    # only a fraction of each peer's splats — the regime the exchange
    # exists for.  point_cloud_for returns ~n points, so over-request and
    # slice.
    from repro.data.isosurface import point_cloud_for
    from repro.launch.mesh import make_mesh
    pts, cols = point_cloud_for("kingsnake", int(n_total * 1.5))
    assert pts.shape[0] >= n_total, pts.shape
    pts, cols = pts[:n_total], cols[:n_total]
    if spatial_sort:
        # overlap-aware layout: Morton-order the rows so each contiguous
        # "part" shard is a compact spatial brick (core.partition) — the
        # condition under which per-edge overlap shrinks with n_part
        order = spatial_order(pts)
        pts, cols = pts[order], cols[order]
    cams = orbital_rig(views, (0.5, 0.5, 0.5), 0.8, width=res, height=res)
    cam_b = select(cams, jnp.arange(views))
    g_all = from_points(jnp.asarray(pts), jnp.asarray(cols),
                        init_scale=0.004 if res >= 256
                        else 0.008 if res >= 128 else 0.01,
                        opacity=0.8)
    g_b = jax.tree.map(lambda x: x[None], g_all)       # (P=1, N, ...)

    mesh = make_mesh((n_dev,), ("part",))
    g_sh, opt_sh, b_sh = gs_shardings(mesh, views=views)
    g_dev = jax.device_put(g_b, g_sh)
    cam_dev = jax.device_put(cam_b, b_sh["cam"])

    # ---- probe the per-edge demand matrix; payload is rows * row_bytes.
    # The bench sizes budgets at EXACT demand (slack=1, round_to=1): the
    # wire payload then measures the true probed overlap, not the
    # schedule's safety margin (production keeps the slack; parity below
    # holds either way because the probe covers the timed views).
    probe = jax.jit(make_gs_exchange_probe(mesh, grid, views=views,
                                           per_edge=True))
    demand = np.asarray(probe(g_dev, cam_dev))
    es = ExchangeSchedule(slack=1.0, round_to=1)
    B = np.asarray(es.probe_budget(demand, n_local))
    # the transport's slab heights: ring shift k moves every
    # (s -> (s+k) % n) edge in one slab sized by that shift's worst edge
    # (core.distributed ppermute ladder), with the overlap-aware window
    # assignment tau pulling each brick's dominant band onto the free
    # local shift — the same tau the forward derives from this budget
    ring = (np.arange(n_dev) + np.arange(n_dev)[:, None]) % n_dev
    tau = window_assignment(np.minimum(B, n_local))
    e_shift = np.array([B[np.arange(n_dev), tau[ring[k]]].max()
                        for k in range(n_dev)], np.int64)
    rows_wire = int(e_shift[1:].sum())           # communicated rows/device
    rows_all = int(e_shift.sum())                # incl. the local slab
    F = splat_features(project(g_all, select(cams, 0))).shape[-1]
    # per-dtype row accounting: the wire dtype follows cfg.dtype_policy
    # (core.dtypes) — f32 rows are (F + 3) * 4 bytes (feat + aux), bf16
    # halves every lane (bench_dtype times the policies; here the bf16
    # payload rides along so the exchange table reports both)
    row_bytes = (F + 3) * 4
    row_bytes_bf16 = (F + 3) * 2
    bytes_gather = (n_dev - 1) * views * n_local * row_bytes
    bytes_exchange = rows_wire * views * row_bytes

    # ---- one train step, gather vs exchange (parity gates the timing) ----
    gt = jnp.zeros((views, grid.n_tiles, 3, grid.tile_h, grid.tile_w))
    mask = jnp.ones((views, grid.n_tiles, grid.tile_h, grid.tile_w), bool)
    batch = {"gt_tiles": jax.device_put(gt, b_sh["gt_tiles"]),
             "mask_tiles": jax.device_put(mask, b_sh["mask_tiles"]),
             "cam": cam_dev}

    def fresh_state():
        # fresh buffers each config: the step DONATES g/opt, and device_put
        # aliases (doesn't copy) leaves whose sharding already matches, so
        # reusing one host tree across configs would hand the second run
        # deleted buffers
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

    def timed(cfg, budget):
        step = make_gs_train_step(mesh, cfg, grid, extent=1.0, impl="ref",
                                  views=views, exchange_budget=budget)
        # the step donates g/opt, so thread the returned state through
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

    t_g, l_g = timed(GSTrainCfg(K=K), None)
    t_e, l_e = timed(GSTrainCfg(K=K, exchange=True), B)
    np.testing.assert_allclose(l_e, l_g, rtol=1e-6, atol=1e-7)

    print("RESULT " + json.dumps({
        "n_devices": n_dev, "n_local": n_local, "n_total": n_total,
        "views": views, "res": res, "n_tiles": grid.n_tiles,
        "spatial_sort": spatial_sort,
        "max_edge_overlap": int(demand.max()),
        "mean_edge_overlap": float(demand.mean()),
        "budget": int(B.max()), "budget_matrix_rows_wire": rows_wire,
        "budget_matrix_rows_all": rows_all,
        "overlap_frac": int(demand.max()) / n_local,
        "payload_bytes_gather": bytes_gather,
        "payload_bytes_exchange": bytes_exchange,
        "payload_bytes_gather_bf16":
            (n_dev - 1) * views * n_local * row_bytes_bf16,
        "payload_bytes_exchange_bf16": rows_wire * views * row_bytes_bf16,
        "payload_reduction": bytes_gather / max(bytes_exchange, 1),
        "t_step_gather_s": t_g, "t_step_exchange_s": t_e,
        "step_speedup": t_g / t_e, "loss": l_g}))


def _run_one(*, res: int, n_total: int, n_dev: int, views: int, reps: int,
             spatial_sort: bool) -> dict:
    cmd = [sys.executable, "-m", "benchmarks.bench_exchange", "--inner",
           "--res", str(res), "--n-total", str(n_total),
           "--n-part", str(n_dev), "--views", str(views),
           "--reps", str(reps)]
    if not spatial_sort:
        cmd.append("--no-spatial-sort")
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_dev}",
               JAX_PLATFORMS="cpu")
    env.setdefault("PYTHONPATH", "src")
    print(f"\n[exchange] res={res} n_total={n_total} x{n_dev} parts "
          f"V={views} sort={spatial_sort} "
          f"(subprocess, {n_dev} forced host devices)")
    proc = subprocess.run(cmd, env=env, capture_output=True, text=True,
                          timeout=1800)
    sys.stdout.write(proc.stdout[: proc.stdout.rfind("RESULT ")])
    sys.stderr.write(proc.stderr[-2000:] if proc.returncode else "")
    if proc.returncode:
        raise SystemExit(f"bench_exchange inner failed ({proc.returncode})")
    r = json.loads(proc.stdout.rstrip().rsplit("RESULT ", 1)[1])

    mb = 1.0 / (1024 * 1024)
    print(f"  probed edge overlap: worst {r['max_edge_overlap']}"
          f"/{r['n_local']} ({r['overlap_frac']:.1%}), "
          f"mean {r['mean_edge_overlap']:.1f}")
    print("  per-device payload: all-gather "
          f"{r['payload_bytes_gather'] * mb:7.2f} MiB  exchange "
          f"{r['payload_bytes_exchange'] * mb:7.2f} MiB  "
          f"({r['payload_reduction']:.2f}x smaller, proportional to the "
          "probed per-edge overlap)")
    print(f"  train step: gather {r['t_step_gather_s'] * 1e3:8.2f} ms  "
          f"exchange {r['t_step_exchange_s'] * 1e3:8.2f} ms  "
          f"({r['step_speedup']:.2f}x; host-device collectives are "
          "memcpy-emulated — payload is the headline)")
    return r


def run(*, res: int = 256, n_local: int = 4096, views: int = 4,
        reps: int = 3, quick: bool = False,
        gate_floor: float | None = None,
        n_parts: tuple = (4,), spatial_sort: bool = True):
    """Sweep the exchange over ``n_parts`` partition counts at a FIXED
    total splat count (``n_local`` is the per-part count at the first
    entry).  With more than one entry the sweep GATES on the per-device
    exchange payload strictly decreasing as n_part grows — the scaling
    property per-edge budgets + overlap-aware partitioning exist for.
    ``gate_floor`` additionally requires the first entry's payload
    reduction over the all-gather to meet the floor.  Returns the first
    entry's result dict (the orchestrator's wall-clock entry), with the
    full sweep under ``"sweep"``."""
    if quick:
        res, n_local, views, reps = 64, 256, 2, 2
    n_parts = tuple(int(n) for n in n_parts)
    n_total = n_local * n_parts[0]
    results = []
    for n_dev in n_parts:
        if n_total % n_dev:
            raise SystemExit(f"--n-part {n_dev} must divide the total "
                             f"splat count {n_total}")
        results.append(_run_one(res=res, n_total=n_total, n_dev=n_dev,
                                views=views, reps=reps,
                                spatial_sort=spatial_sort))

    r = dict(results[0])
    r["sweep"] = [
        {k: x[k] for k in ("n_devices", "n_local", "payload_bytes_exchange",
                           "payload_bytes_gather", "payload_reduction",
                           "max_edge_overlap", "mean_edge_overlap",
                           "budget_matrix_rows_wire", "t_step_exchange_s",
                           "t_step_gather_s")}
        for x in results]
    save_result("exchange", r)
    if len(results) > 1:
        pay = [x["payload_bytes_exchange"] for x in results]
        print(f"\n[exchange] payload sweep over n_part={list(n_parts)}: "
              + " -> ".join(f"{p / (1 << 20):.2f} MiB" for p in pay))
        for a, b, na, nb in zip(pay, pay[1:], n_parts, n_parts[1:]):
            if b >= a:
                raise SystemExit(
                    "exchange scale gate FAILED: per-device payload did "
                    f"not decrease from n_part={na} ({a}B) to n_part={nb} "
                    f"({b}B) — per-edge budgets + spatial partitioning "
                    "are not delivering overlap that shrinks with scale")
    if gate_floor is not None and r["payload_reduction"] < gate_floor:
        raise SystemExit(
            f"exchange payload gate FAILED: {r['payload_reduction']:.2f}x "
            f"reduction below floor {gate_floor:.2f}x — the probed budget "
            "no longer undercuts the full table")
    return r


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--res", type=int, default=256)
    ap.add_argument("--points-per-part", type=int, default=4096,
                    help="per-part splats at the FIRST --n-part entry; the "
                         "total count stays fixed across the sweep")
    ap.add_argument("--views", type=int, default=4)
    ap.add_argument("--reps", type=int, default=3)
    ap.add_argument("--n-part", default="4",
                    help="comma-separated partition counts to sweep, e.g. "
                         "4,8,16 (each runs a subprocess with that many "
                         "forced host devices)")
    ap.add_argument("--no-spatial-sort", action="store_true",
                    help="skip the Morton row sort (shows the scrambled-"
                         "layout overlap the sweep gate would fail on)")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--gate-floor", type=float, default=None,
                    help="fail unless the exchange payload is at least this "
                         "factor smaller than the all-gather's")
    ap.add_argument("--n-total", type=int, default=None,
                    help=argparse.SUPPRESS)      # inner-only
    ap.add_argument("--inner", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    if args.inner:
        _inner(res=args.res, n_total=args.n_total,
               n_dev=int(args.n_part), views=args.views, reps=args.reps,
               spatial_sort=not args.no_spatial_sort)
        return
    run(res=args.res, n_local=args.points_per_part, views=args.views,
        reps=args.reps, quick=args.smoke, gate_floor=args.gate_floor,
        n_parts=tuple(int(x) for x in args.n_part.split(",")),
        spatial_sort=not args.no_spatial_sort)


if __name__ == "__main__":
    main()

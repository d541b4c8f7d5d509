"""Time-series warm-start benchmark (PR 9): convergence + bounded memory.

Two claims of the ``--timeseries`` driver, measured on a small evolving
sphere_shell scene through the real distributed driver
(``core/distributed.fit_partitions``):

  convergence   timestep t=1 warm-started from t=0's trained state must
                reach the COLD run's final loss (fresh init on the same
                t=1 scene, ``steps_cold`` steps) in at most
                ``gate_frac`` (default 0.6) of its steps — the
                per-timestep retraining saving that makes in-situ use
                plausible (PAPERS.md: arXiv 2509.05216 frames this cost
                as the obstacle);
  boundedness   a multi-timestep run with densification ON and
                ``densify_cap`` set holds the live-splat count exactly
                flat at the cap across timesteps (GeoGaussian-style
                num_max) while the UNCAPPED twin keeps growing — the
                memory wild card of distributed 3D-GS training
                (arXiv 2406.18533) stays bounded.

Exits nonzero when warm-start needs more than ``gate_frac`` of the cold
steps or the cap is exceeded; ``benchmarks/run.py`` (smoke tier)
downgrades that to a warning and the committed-baseline comparison
(tools/check_bench.py) gates CI.  Saves JSON under
experiments/benchmarks/timeseries.json.

    PYTHONPATH=src python -m benchmarks.bench_timeseries [--smoke]
        [--steps 24] [--gate-frac 0.6]
"""

from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import save_result
from repro.configs.gs_datasets import get_gs_dataset
from repro.core.cameras import orbital_rig
from repro.core.distributed import fit_partitions
from repro.core.pipeline import build_scene, prepare_timestep
from repro.core.tiling import TileGrid
from repro.core.train import GSTrainCfg
from repro.launch.mesh import make_mesh


def _fit(td, cams, grid, cfg, mesh, *, steps, key, warm=None,
         densify_every=0, densify_from=0, densify_cap=None):
    return fit_partitions(
        td.g0, cams, jnp.asarray(td.gts),
        None if td.masks is None else jnp.asarray(td.masks), cfg,
        mesh=mesh, steps=steps, extent=td.extent, key=key, grid=grid,
        schedule=cfg.tier_schedule(), warm_start=warm,
        densify_every=densify_every, densify_from=densify_from,
        densify_cap=densify_cap)


def run(*, steps: int = 24, res: int = 32, n_views: int = 4,
        dt: float = 0.02, gate_frac: float = 0.6, quick: bool = False):
    if quick:
        steps = min(steps, 16)
    S = steps
    ds = get_gs_dataset("sphere_shell", "cpu")
    # series-fixed frame from the t=0 scene, exactly like the driver
    points, _, extent = build_scene(ds, 0, t=0.0)
    center = 0.5 * (points.max(0) + points.min(0))
    cams = orbital_rig(n_views, center, 1.6 * extent / 2 + 1e-3,
                       width=res, height=res)
    grid = TileGrid(res, res, 8, 16)
    cfg = GSTrainCfg(K=16, lambda_dssim=0.0, bg=0.0, view_batch=2,
                     lr_colors=5e-2)
    mesh = make_mesh((len(jax.devices()), 1), ("part", "view"))
    cap0 = -(-int(ds.n_points * ds.capacity_factor) // len(jax.devices())) \
        * len(jax.devices())
    key = jax.random.PRNGKey(0)

    def prep(t_idx):
        return prepare_timestep(ds, cams, grid, t=t_idx * dt, n_parts=1,
                                capacity=cap0, K=cfg.K)

    print(f"\n[timeseries] sphere_shell res={res} steps/timestep={S} "
          f"dt={dt} capacity={cap0}")

    # ---- convergence: cold vs warm on the SAME t=1 scene.  The warm seed
    # gets 2S steps at t=0 — a running series has accumulated training,
    # which is exactly the asset warm-starting carries forward; the cold
    # baseline re-inits from the t=1 extraction (our analytic extraction
    # is a STRONG init — exact positions and colors — so this gate is
    # conservative vs real in-situ data).  Each run gets a fresh
    # prepare_timestep: the donating step consumes the init buffers.
    t0 = time.perf_counter()
    _, _, cold = _fit(prep(1), cams, grid, cfg, mesh, steps=S, key=key)
    target = cold[-1]
    g_t0, opt_t0, _ = _fit(prep(0), cams, grid, cfg, mesh, steps=2 * S,
                           key=key)
    warm_tree = jax.tree.map(jax.device_get, (g_t0, opt_t0))
    extra = {"dtype_policy": cfg.dtype_policy,
             "grad_compress": cfg.grad_compress}
    _, _, warm = _fit(prep(1), cams, grid, cfg, mesh, steps=3 * S, key=key,
                      warm=(warm_tree, extra, 2 * S))
    hit = [i + 1 for i, l in enumerate(warm) if l <= target]
    steps_warm = hit[0] if hit else len(warm) + 1
    ratio = steps_warm / S
    print(f"  cold: {S} steps -> final loss {target:.4f}")
    print(f"  warm: reaches it in {steps_warm} steps "
          f"({ratio:.2f}x of cold, gate <= {gate_frac:.2f}x)")

    # ---- boundedness: capped vs uncapped densify across 3 timesteps ----
    dcfg = GSTrainCfg(K=16, lambda_dssim=0.0, bg=0.0, view_batch=2,
                      lr_colors=5e-2, max_new=256, densify_grad_thresh=1e-9)
    Sd = max(4, S // 4)
    live_capped, live_free = [], []
    cap = None
    for capped in (True, False):
        warm_t, lives = None, []
        for t in range(3):
            td = prep(t)
            if cap is None:
                cap = int(np.asarray(td.g0.active).sum())
            g1, o1, _ = _fit(td, cams, grid, dcfg, mesh,
                             steps=(t + 1) * Sd, key=key, warm=warm_t,
                             densify_every=2, densify_from=0,
                             densify_cap=cap if capped else None)
            lives.append(int(np.asarray(g1.active).sum()))
            warm_t = (jax.tree.map(jax.device_get, (g1, o1)),
                      {"dtype_policy": dcfg.dtype_policy,
                       "grad_compress": dcfg.grad_compress}, (t + 1) * Sd)
        (live_capped if capped else live_free).extend(lives)
    print(f"  densify_cap={cap}: live {live_capped} (capped)  "
          f"vs {live_free} (uncapped)")

    results = {
        "steps_cold": S, "target_loss": float(target),
        "steps_to_target_warm": int(steps_warm),
        "warm_over_cold_steps": float(ratio), "gate_frac": gate_frac,
        "densify_cap": int(cap), "live_capped": live_capped,
        "live_uncapped": live_free,
        "wall_clock_s": time.perf_counter() - t0,
    }
    save_result("timeseries", results)
    if ratio > gate_frac:
        raise SystemExit(
            f"[timeseries] GATE: warm-start needed {steps_warm}/{S} steps "
            f"({ratio:.2f}x) to reach the cold final loss — over the "
            f"{gate_frac:.2f}x floor; warm-starting stopped paying")
    if max(live_capped) > cap:
        raise SystemExit(
            f"[timeseries] GATE: live splats {max(live_capped)} exceeded "
            f"densify_cap={cap} — the cap no longer bounds memory")
    if len(set(live_capped)) != 1:
        raise SystemExit(
            "[timeseries] GATE: capped live count drifted across "
            f"timesteps ({live_capped}) — expected flat at the cap")
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--steps", type=int, default=24)
    ap.add_argument("--gate-frac", type=float, default=0.6)
    args = ap.parse_args()
    run(steps=args.steps, gate_frac=args.gate_frac, quick=args.smoke)


if __name__ == "__main__":
    main()

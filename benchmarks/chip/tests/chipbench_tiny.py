"""Tiny CPU versions of the benchmark's cells, for the harness tests: the
real configurations and mixes with the scene, images and window cut down
and the Pallas kernels in interpret mode (or the program's ``ref``)."""

from __future__ import annotations

import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
CHIP = HERE.parent
ROOT = CHIP.parents[1]
for p in (str(ROOT / "src"), str(CHIP)):
    if p not in sys.path:
        sys.path.insert(0, p)

import harness  # noqa: E402

#: limits of the tiny scene: float32 rounding reads about 1e-6 here (losses,
#: gradient norms, pixels), a broken step or answer reads 1e-2 and more
TINY_LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-3, "change_gap": 1e-2,
               "img_mean_gap": 1e-5}


def tiny_cfg(config: str, impl: str = "interpret") -> dict:
    cfg = harness.load_config(harness.load_benchmark(ROOT), config, ROOT)
    cfg.update(n_points=2000, grid_resolution=40, resolution=32,
               tile=[8, 16], limits=dict(TINY_LIMITS))
    if cfg["kind"] == "train":
        cfg.update(capacity=1152, n_views=4)
        cfg["program"].update(impl=impl, view_batch=4)
    else:
        cfg["server"]["impl"] = impl
    return cfg


def tiny_traffic(mix: str) -> dict:
    tr = harness.load_traffic(mix)
    if "arrivals" in tr:
        tr["arrivals"]["rate_rps"] = 5.0
    return tr


def run_cell(config: str, mix: str, cache: Path, *, seed: int = 7,
             seconds: float = 1.0, impl: str = "interpret",
             overrides: dict = None, traffic: dict = None) -> tuple:
    """-> (out, ctx) of one tiny run through the kind's driver."""
    import importlib
    cfg = tiny_cfg(config, impl)
    ctx = harness.Ctx(workload=f"{config}.{mix}", cfg=cfg,
                      traffic=traffic or tiny_traffic(mix), seed=seed,
                      seconds=seconds,
                      trace=False, overrides=dict(overrides or {}))
    ctx.compiles = harness.CompileCounter()
    mod = importlib.import_module(f"{cfg['kind']}_cell")
    return mod.run(ctx, cache=cache), ctx


def correct(out) -> bool:
    return all(c.ok for c in out["checks"])

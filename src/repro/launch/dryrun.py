import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST precede every other import (jax locks the device
# count at first init).  REPRO_DRYRUN_DEVICES overrides for reduced tests.
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_DRYRUN_DEVICES"]
    )

"""Multi-pod dry-run: lower + compile every (architecture x input-shape) cell
on the production meshes and record memory / cost / collective analyses.

  python -m repro.launch.dryrun --arch all --shape all --mesh both
  python -m repro.launch.dryrun --gs --mesh both
  python -m repro.launch.dryrun --arch mixtral-8x22b --shape train_4k

Per-cell JSON lands in experiments/dryrun/<mesh>/<arch>__<shape>.json and is
cached (re-runs skip finished cells unless --force).  benchmarks/roofline.py
consumes these files.
"""

import argparse
import json
import time
import traceback

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import all_arch_ids, get_spec
from repro.configs.gs_datasets import FULL as GS_FULL
from repro.launch import hlo_analysis
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.models.params import param_shardings, param_specs
from repro.models.steps import (
    SHAPES,
    TrainCfg,
    cache_pspecs,
    input_pspecs,
    input_specs,
    make_decode_step,
    make_prefill_step,
    make_train_step,
    opt_state_shardings,
    opt_state_specs,
)

# TPU v5e roofline constants (assignment)
PEAK_FLOPS = 197e12        # bf16 / chip
HBM_BW = 819e9             # bytes/s / chip
ICI_BW = 50e9              # bytes/s / link

GS_CELLS = {
    # name -> (dataset, resolution)
    "gs-kingsnake": ("kingsnake", 2048),
    "gs-rayleigh-taylor": ("rayleigh_taylor", 2048),
    "gs-richtmyer-meshkov": ("richtmyer_meshkov", 2048),
    "gs-richtmyer-meshkov-1k": ("richtmyer_meshkov", 1024),
}


def make_meshes(which: str):
    out = {}
    n = len(jax.devices())
    if n == 512:
        if which in ("single", "both"):
            out["single"] = make_production_mesh(multi_pod=False)
        if which in ("multi", "both"):
            out["multi"] = make_production_mesh(multi_pod=True)
    else:  # reduced test meshes (REPRO_DRYRUN_DEVICES)
        if which in ("single", "both"):
            out["single"] = make_mesh((2, n // 2), ("data", "model"))
        if which in ("multi", "both"):
            out["multi"] = make_mesh((2, 2, n // 4),
                                     ("pod", "data", "model"))
    return out


def _mem_analysis(compiled):
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    return out


def _ns_tree(mesh, pspec_tree):
    return jax.tree.map(
        lambda ps: NamedSharding(mesh, ps), pspec_tree,
        is_leaf=lambda x: isinstance(x, P),
    )


def model_flops(spec, shape_name: str) -> float:
    """Assignment definition: 6*N*D train / 2*N*D inference, N active params,
    D tokens processed globally."""
    sh = SHAPES[shape_name]
    n = spec.param_count(active_only=True)
    if sh["kind"] == "train":
        return 6.0 * n * sh["batch"] * sh["seq"]
    if sh["kind"] == "prefill":
        return 2.0 * n * sh["batch"] * sh["seq"]
    return 2.0 * n * sh["batch"]  # decode: one token per sequence


def lower_lm_cell(spec, shape_name: str, mesh):
    with mesh:   # mesh context so in-model sharding constraints bind
        return _lower_lm_cell(spec, shape_name, mesh)


def _lower_lm_cell(spec, shape_name: str, mesh):
    sh = SHAPES[shape_name]
    kind = sh["kind"]
    iospecs = input_specs(spec, shape_name)
    iopspec = input_pspecs(spec, mesh, shape_name)

    if kind == "train":
        cfg = TrainCfg(total_steps=10_000)
        step = make_train_step(spec, cfg)
        p_sh = param_shardings(spec, mesh)
        o_sh = opt_state_shardings(spec, mesh, cfg)
        b_sh = _ns_tree(mesh, iopspec["batch"])
        metrics_sh = {k: NamedSharding(mesh, P())
                      for k in ("loss", "aux", "grad_norm", "lr_scale")}
        jitted = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                         out_shardings=(p_sh, o_sh, metrics_sh),
                         donate_argnums=(0, 1))
        return jitted.lower(param_specs(spec), opt_state_specs(spec, cfg),
                            iospecs["batch"])
    if kind == "prefill":
        step = make_prefill_step(spec)
        p_sh = param_shardings(spec, mesh)
        b_sh = _ns_tree(mesh, iopspec["batch"])
        jitted = jax.jit(step, in_shardings=(p_sh, b_sh))
        return jitted.lower(param_specs(spec), iospecs["batch"])
    # decode
    step = make_decode_step(spec)
    p_sh = param_shardings(spec, mesh)
    c_sh = _ns_tree(mesh, cache_pspecs(spec, mesh, sh["batch"]))
    t_sh = _ns_tree(mesh, iopspec["tokens"])
    pos_sh = NamedSharding(mesh, P())
    jitted = jax.jit(step, in_shardings=(p_sh, c_sh, t_sh, pos_sh),
                     donate_argnums=(1,))
    return jitted.lower(param_specs(spec), iospecs["caches"],
                        iospecs["tokens"], iospecs["pos"])


def lower_gs_cell(cell: str, mesh, *, opt: bool = False):
    from repro.core.distributed import (
        gs_batch_specs, gs_state_specs, make_gs_train_step,
    )
    from repro.core.tiling import TileGrid
    from repro.core.train import GSTrainCfg

    ds_name, res = GS_CELLS[cell]
    ds = GS_FULL[ds_name]
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_parts = sizes.get("pod", 1)
    # round shard-divisible (shard_map over the "data" axis)
    mult = sizes["data"] * 4096
    n_per_part = -(-ds.n_points // n_parts // mult) * mult
    grid = TileGrid(res, res, 8, 128)
    if opt:   # beyond-paper optimized variant (§Perf GS hillclimb)
        n_model = sizes["model"]
        cfg = GSTrainCfg(K=64, tile_h=8, tile_w=128, gather_mode="split",
                         strip_budget=min(1.0, 4.0 / n_model))
    else:
        cfg = GSTrainCfg(K=64, tile_h=8, tile_w=128)
    # k_tiers=None: lower the DENSE step — the analytic flop model and the
    # recorded meta K below describe dense-K rasterization, and the tiered
    # dispatch's work depends on runtime occupancy the dry run cannot see
    step = make_gs_train_step(mesh, cfg, grid, extent=1.0, impl="ref",
                              k_tiers=None)
    g, opt = gs_state_specs(n_parts, n_per_part)
    batch = gs_batch_specs(n_parts, grid)
    lowered = step.lower(g, opt, batch)
    meta = {
        "dataset": ds_name, "resolution": res, "n_parts": n_parts,
        "gaussians_per_part": n_per_part, "K": cfg.K,
        "tiles": grid.n_tiles,
    }
    # analytic "useful" flops (fwd+bwd rasterize + projection + loss; the
    # dense tile-assignment is implementation overhead, not model flops)
    T, K, pix = grid.n_tiles, cfg.K, grid.tile_h * grid.tile_w
    raster = n_parts * T * K * pix * (30 + 45)
    proj = n_parts * n_per_part * 300 * 3          # fwd + bwd
    loss = n_parts * T * pix * 3 * 2 * 49 * 6      # ssim convs fwd+bwd
    return lowered, meta, float(raster + proj + loss)


def lower_gs_train_cell(dataset: str, mesh, *, res: int = 64,
                        n_parts: int = 2, view_batch: int = 0,
                        tier: str = "cpu"):
    """Lower the PRODUCTION GS train step — the same tiered
    ``make_gs_train_step`` the distributed driver (``fit_partitions``) and
    the timeseries loop dispatch every step — on a ("part", "view") mesh.

    Unlike ``lower_gs_cell`` (dense-K, analysis-friendly flop model, dryrun
    meshes) this profiles what training actually runs: occupancy-tiered
    rasterization (strip-sized caps: the always-exact shape, an upper bound
    on any probed-cap step), the view-minibatch forward, and the trainer's
    collective layout.  -> (lowered, meta).
    """
    from repro.configs.gs_datasets import get_gs_dataset
    from repro.core.distributed import (gs_batch_specs, gs_state_specs,
                                        make_gs_train_step)
    from repro.core.tiling import TileGrid
    from repro.core.train import GSTrainCfg

    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    vb = view_batch or sizes.get("view", 1)
    cfg = GSTrainCfg(view_batch=vb)
    ds = get_gs_dataset(dataset, tier)
    mult = sizes.get("part", 1)           # N is sharded over "part"
    n_per_part = -(-int(ds.n_points * ds.capacity_factor)
                   // n_parts // mult) * mult
    grid = TileGrid(res, res, cfg.tile_h, cfg.tile_w)
    step = make_gs_train_step(mesh, cfg, grid, extent=1.0, impl="ref",
                              views=vb, return_overflow=True)
    g, opt = gs_state_specs(n_parts, n_per_part)
    batch = gs_batch_specs(n_parts, grid, views=vb)
    meta = {
        "dataset": dataset, "resolution": res, "n_parts": n_parts,
        "gaussians_per_part": n_per_part, "view_batch": vb,
        "k_tiers": cfg.resolved_k_tiers(), "tiles": grid.n_tiles,
    }
    return step.lower(g, opt, batch), meta


def run_cell(arch: str, shape: str, mesh, mesh_tag: str, out_dir: str,
             force: bool = False, gs_opt: bool = False) -> str:
    os.makedirs(f"{out_dir}/{mesh_tag}", exist_ok=True)
    path = f"{out_dir}/{mesh_tag}/{arch}__{shape}.json"
    if os.path.exists(path) and not force:
        with open(path) as f:
            return json.load(f)["status"] + " (cached)"

    rec = {
        "arch": arch, "shape": shape, "mesh": mesh_tag,
        "mesh_shape": list(mesh.devices.shape),
        "mesh_axes": list(mesh.axis_names),
        "n_devices": int(mesh.devices.size),
    }
    is_gs = arch.startswith("gs-")
    if not is_gs:
        spec = get_spec(arch)
        if shape in spec.skip_shapes:
            rec.update(status="skip",
                       reason="long_500k needs sub-quadratic attention "
                              "(pure full-attention arch; DESIGN.md §5)")
            with open(path, "w") as f:
                json.dump(rec, f, indent=1)
            return "skip"

    pod_size = 1
    if "pod" in mesh.axis_names:
        sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
        pod_size = int(mesh.devices.size // sizes["pod"])

    try:
        t0 = time.time()
        if is_gs:
            lowered, meta, mflops = lower_gs_cell(arch, mesh, opt=gs_opt)
            rec["gs_meta"] = meta
        else:
            lowered = lower_lm_cell(spec, shape, mesh)
            mflops = model_flops(spec, shape)
        rec["lower_s"] = round(time.time() - t0, 2)

        t0 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t0, 2)

        rec["memory_analysis"] = _mem_analysis(compiled)
        try:
            ca = compiled.cost_analysis()
            rec["xla_cost_analysis"] = {
                k: float(v) for k, v in ca.items()
                if isinstance(v, (int, float)) and (
                    "flops" in k or "bytes accessed" == k or "utilization" in k)
            }
        except Exception:
            rec["xla_cost_analysis"] = {}

        t0 = time.time()
        hlo = hlo_analysis.analyze(
            compiled.as_text(),
            pod_size=pod_size if "pod" in mesh.axis_names else 0)
        rec["analyze_s"] = round(time.time() - t0, 2)
        rec["hlo"] = hlo

        n = rec["n_devices"]
        rec["model_flops_global"] = mflops
        rec["model_flops_per_device"] = mflops / n
        rec["roofline"] = {
            "compute_s": hlo["flops"] / PEAK_FLOPS,
            "memory_s": hlo["hbm_bytes"] / HBM_BW,
            "collective_s": hlo["collective_wire_bytes"] / ICI_BW,
        }
        dom = max(rec["roofline"], key=rec["roofline"].get)
        rec["bottleneck"] = dom
        rec["useful_flops_ratio"] = (
            rec["model_flops_per_device"] / hlo["flops"]
            if hlo["flops"] else 0.0)
        rec["status"] = "ok"
    except Exception:
        rec["status"] = "error"
        rec["traceback"] = traceback.format_exc()[-4000:]
    finally:
        jax.clear_caches()

    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    if rec["status"] == "error":
        return "error: " + rec["traceback"].strip().splitlines()[-1][:150]
    r = rec["roofline"]
    return (f"ok  lower {rec['lower_s']:.0f}s compile {rec['compile_s']:.0f}s  "
            f"compute {r['compute_s']*1e3:.2f}ms mem {r['memory_s']*1e3:.2f}ms "
            f"coll {r['collective_s']*1e3:.2f}ms -> {rec['bottleneck']}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="csv of arch ids, 'all' (LM), or gs cell names")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--gs", action="store_true", help="run the GS cells")
    ap.add_argument("--gs-opt", action="store_true",
                    help="optimized GS variant (split gather + strip prefilter)")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true")
    args = ap.parse_args()

    cells = []
    if args.gs:
        archs = list(GS_CELLS)
        shapes = ["train"]
    else:
        archs = all_arch_ids() if args.arch == "all" else args.arch.split(",")
        shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = make_meshes(args.mesh)
    for mesh_tag, mesh in meshes.items():
        for arch in archs:
            for shape in shapes:
                cells.append((arch, shape, mesh, mesh_tag))

    print(f"dry-run: {len(cells)} cells on {len(jax.devices())} devices")
    for i, (arch, shape, mesh, mesh_tag) in enumerate(cells):
        t0 = time.time()
        msg = run_cell(arch, shape, mesh, mesh_tag, args.out, args.force,
                       gs_opt=args.gs_opt)
        print(f"[{i+1}/{len(cells)}] {mesh_tag:6s} {arch:28s} {shape:12s} "
              f"{msg}  ({time.time()-t0:.0f}s)", flush=True)


if __name__ == "__main__":
    main()

"""Chip smoke test: the distributed 3D-GS trainer and server, end to end, on
TPU, through the same entry points a user calls.

    python chip_smoke.py               # one chip
    python chip_smoke.py --four-chips  # the 2x2 ("part", "view") mesh only

One chip: first the Pallas rasterizer against ``ref``, forward and
gradient, at the (8, 128) and (8, 16) tiles; then ``repro.launch.train``
trains kingsnake at ``--full`` (4M
isosurface points, 512x512 images, (8, 128) tiles, Pallas kernels) as two
partitions with ghost cells and masks on a 1x1 mesh — 21 steps, one densify
event, a checkpoint — merges the partitions, then
``repro.launch.serve_gs --full`` serves the merged checkpoint twice (the
repeat pass must be all cache hits).

Four chips: the same scene as four partitions trains a few steps on a 2x2
mesh, with the all-gather and again with the sparse exchange, and both are
compared with the same partitions trained on a 1x1 mesh of the first chip.

The run fails (nonzero exit) unless JAX's devices are TPUs and every check
holds; it prints the device, sizes, compile seconds, per-phase seconds
(smoke timings, not benchmark numbers), losses and parity errors, and as
its last line ``{"ok": true, "device": {...}}``.  Everything runs in this
one process, which holds the chip; it starts no child process.

``one_chip`` and ``four_chips`` take a ``Size``: ``TINY`` and ``TINY4``
rehearse the same phases on CPU at a small size (``impl="interpret"``,
the CPU tile), which is what the tests drive; they run ``kernels`` with
``impl="interpret"`` and a few tiles.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
OUT = ROOT / "chip_smoke_out"


@dataclasses.dataclass(frozen=True)
class Size:
    """One smoke configuration: launch/train.py argv (minus --ckpt-dir)
    and the serving flags."""
    train: tuple
    serve: tuple


# 21 steps: the last step trains the first step's minibatch (views 0-3)
# again, so "last loss below first" compares like with like
FULL = Size(
    train=("--gs", "--dataset", "kingsnake", "--full", "--parts", "2",
           "--views", "16", "--view-batch", "4", "--steps", "21",
           "--densify-every", "12", "--densify-from", "0",
           "--ckpt-every", "10", "--log-every", "5"),
    serve=("--full", "--views", "4", "--passes", "2"))

# the four-chip phase: no densify, so both meshes train identical layouts;
# one 4-view minibatch (2 views per device on the 2x2 mesh)
FULL4 = Size(
    train=("--gs", "--dataset", "kingsnake", "--full", "--parts", "4",
           "--views", "4", "--view-batch", "4", "--steps", "3",
           "--mesh", "2x2"),
    serve=())

# the densify event follows the last step: at this size one event clones
# about half the splats (max_new=512), which would mask the loss check
TINY = Size(
    train=("--gs", "--dataset", "sphere_shell", "--parts", "2",
           "--resolution", "32", "--views", "4", "--view-batch", "2",
           "--steps", "7", "--densify-every", "7", "--densify-from", "0",
           "--ckpt-every", "3", "--impl", "interpret"),
    serve=("--impl", "interpret", "--views", "4", "--passes", "2"))

TINY4 = Size(
    train=("--gs", "--dataset", "sphere_shell", "--parts", "4",
           "--resolution", "32", "--views", "4", "--view-batch", "4",
           "--steps", "3", "--mesh", "2x2", "--impl", "interpret"),
    serve=())

#: max |pallas - ref| over one rendered view (rgb in [0, 1]).  Both
#: rasterizers share projection and tile assignment, so they differ only
#: in f32 rounding (exp, divide, fused multiply-add) compounded over at
#: most K = 64 compositing steps: ~1e-5.  1e-3 is a quarter of one 8-bit
#: display level, well under what a visible error or a tile/index bug
#: (errors of 1e-1 and more) would give.
RENDER_ATOL = 1e-3
#: four-chip parity.  Losses: the 2x2 mesh sums per-view losses and
#: gradients across devices in another order than one device does, which
#: moves the loss by float rounding only (rtol 1e-4).  Parameters: Adam
#: divides by sqrt(v), so a gradient that cancels to ~0 can flip sign on
#: rounding and move its element by up to 2 x lr per step; such elements
#: must stay rare — at most 1e-3 of each field may differ by more than 1e-5.
#: Rotations are compared as the covariance they render (divided by the
#: splat's largest scale squared), not as raw quaternions: the splats start
#: isotropic, where the quaternion gradient is pure rounding noise that
#: Adam scales up to lr per step, yet the covariance does not move.
LOSS_RTOL = 1e-4
PARAM_ATOL = 1e-5
PARAM_MAX_FRAC = 1e-3
#: kernel parity, max |d feats| over max |d feats of ref|.  The backward
#: kernel sums each splat's nine gradients over a tile's pixels in its own
#: order, XLA's autodiff of ref in another: f32 rounding, ~1e-6 relative.
#: An index, sign or transmittance bug moves gradients by O(1).
KERNEL_GRAD_RTOL = 1e-3
#: tiles the production and CPU configurations rasterize with
TILES = ((8, 128), (8, 16))


def log(msg: str):
    print(f"[chip-smoke] {msg}", flush=True)


class CompileTimer:
    """Counts XLA backend compiles and their seconds (jax.monitoring)."""

    def __init__(self):
        import jax
        self.n, self.seconds = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1
            self.seconds += duration


class StepRecorder:
    """Lowers each distinct train step that ``fit_partitions`` builds on
    its first call, so the check can read the step that actually ran."""

    def __enter__(self):
        import repro.core.distributed as dist
        self._dist, self._make = dist, dist.make_gs_train_step
        self.lowered = None

        def make(*a, **k):
            fn = self._make(*a, **k)
            seen = []

            def call(*args):
                if not seen:
                    self.lowered = fn.lower(*args)
                    seen.append(True)
                return fn(*args)
            return call

        dist.make_gs_train_step = make
        return self

    def __exit__(self, *exc):
        self._dist.make_gs_train_step = self._make


def _device_line():
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _kernel_inputs(n_tiles: int, K: int, th: int, tw: int, seed: int = 0):
    """Random splat feature lists (n_tiles, K, FEAT_DIM) over tiles laid
    out four across: means in and around the tile, 1-20 px axes, random
    colors and opacities below 1."""
    import numpy as np

    from repro.core.tiling import FEAT_DIM

    rng = np.random.default_rng(seed)
    i = np.arange(n_tiles)
    o = np.stack([(i % 4) * tw, (i // 4) * th], -1).astype(np.float32)
    f = np.zeros((n_tiles, K, FEAT_DIM), np.float32)
    f[..., 0] = o[:, None, 0] + rng.uniform(-20, tw + 20, (n_tiles, K))
    f[..., 1] = o[:, None, 1] + rng.uniform(-10, th + 10, (n_tiles, K))
    sa, sc = rng.uniform(1, 20, (2, n_tiles, K))
    f[..., 2] = 1 / sa ** 2
    f[..., 3] = rng.uniform(-0.5, 0.5, (n_tiles, K)) / (sa * sc)
    f[..., 4] = 1 / sc ** 2
    f[..., 5:8] = rng.uniform(0, 1, (n_tiles, K, 3))
    f[..., 8] = rng.uniform(0, 0.99, (n_tiles, K))
    w = rng.standard_normal((n_tiles, 4, th, tw)).astype(np.float32)
    return f, o, w


def kernels(impl: str, n_tiles: int = 512, K: int = 64) -> dict:
    """The rasterizer's ``impl`` against ``ref``, forward and gradient, at
    each tile of ``TILES``; raises on failure -> {tile: (fwd, grad)}."""
    import jax
    import numpy as np

    from repro.kernels import ops

    report = {}
    for th, tw in TILES:
        f, o, w = _kernel_inputs(n_tiles, K, th, tw)

        def run(impl):
            def loss(x):
                out = ops.rasterize_tiles(x, o, tile_h=th, tile_w=tw,
                                          impl=impl)
                return (out * w).sum(), out
            (_, out), g = jax.jit(jax.value_and_grad(loss, has_aux=True))(f)
            return np.asarray(out), np.asarray(g)[..., :9]

        out_k, g_k = run(impl)
        out_r, g_r = run("ref")
        fwd = float(np.abs(out_k - out_r).max())
        grad = float(np.abs(g_k - g_r).max() / np.abs(g_r).max())
        log(f"kernels {th}x{tw}, {n_tiles} tiles x K={K}: {impl} vs ref "
            f"forward max|diff| {fwd:.3g} (atol {RENDER_ATOL}), gradient "
            f"max rel diff {grad:.3g} (rtol {KERNEL_GRAD_RTOL})")
        if not (fwd <= RENDER_ATOL and grad <= KERNEL_GRAD_RTOL):
            raise AssertionError(f"{impl} kernels at {th}x{tw} differ from "
                                 f"ref: forward {fwd}, gradient {grad}")
        report[(th, tw)] = (fwd, grad)
    return report


def one_chip(size: Size, out: Path = OUT) -> dict:
    """Train -> merge -> parity render -> serve twice; raises on failure."""
    import jax
    import numpy as np

    from repro.core.cameras import select
    from repro.core.pipeline import render_views
    from repro.launch import serve_gs, train

    shutil.rmtree(out, ignore_errors=True)
    ckpt = out / "gs"
    timer = CompileTimer()

    t0 = time.perf_counter()
    args = train.parse_args(list(size.train) + ["--ckpt-dir", str(ckpt)])
    with StepRecorder() as rec:
        res = train.run_gs(args)
    t_train = time.perf_counter() - t0
    grid, cfg, losses = res["grid"], res["cfg"], res["losses"]
    log(f"train: {res['n_init']:,} initial splats -> {res['n_live']:,} "
        f"merged, {grid.n_tiles} tiles of {grid.tile_h}x{grid.tile_w} per "
        f"{grid.width}x{grid.height} view, impl={cfg.impl}")
    log(f"losses: {[round(x, 5) for x in losses]}")

    if not all(np.isfinite(losses)):
        raise AssertionError(f"non-finite loss: {losses}")
    if not losses[-1] < losses[0]:
        raise AssertionError(f"loss did not fall: {losses[0]} -> "
                             f"{losses[-1]}")
    hlo = rec.lowered.compile().as_text()
    has_kernel = "tpu_custom_call" in hlo
    log(f"compiled train step holds tpu_custom_call: {has_kernel}")
    if jax.devices()[0].platform == "tpu" and not has_kernel:
        raise AssertionError("the compiled train step has no Pallas kernel "
                             "(tpu_custom_call)")

    t1 = time.perf_counter()
    view = select(res["cams"], np.arange(1))
    rgb_k = render_views(res["merged"], view, grid, K=cfg.K,
                         impl=cfg.impl)[0]
    rgb_r = render_views(res["merged"], view, grid, K=cfg.K, impl="ref")[0]
    err = float(np.abs(rgb_k - rgb_r).max())
    log(f"parity: merged view 0, {cfg.impl} vs ref max|diff| = {err:.3g} "
        f"(atol {RENDER_ATOL})")
    if not err <= RENDER_ATOL:
        raise AssertionError(f"{cfg.impl} render differs from ref by {err}")
    t_parity = time.perf_counter() - t1

    t2 = time.perf_counter()
    tel = out / "serve.json"
    serve_gs.main(list(size.serve) + ["--ckpt-dir", str(ckpt),
                                      "--telemetry-json", str(tel)])
    passes = json.loads(tel.read_text())["passes"]
    last = passes[-1]
    if last["hits"] != last["requests"]:
        raise AssertionError(f"repeat serving pass: {last}")
    t_serve = time.perf_counter() - t2

    log(f"compiles: {timer.n} backend compiles, {timer.seconds:.1f}s")
    log(f"smoke timings (not benchmark numbers): train+merge+eval "
        f"{t_train:.1f}s, parity {t_parity:.1f}s, serve {t_serve:.1f}s")
    return {"losses": losses, "render_err": err, "passes": passes}


def _unit_covariance(g):
    """Covariance R S S^T R^T over the largest scale squared, in float64
    on the host, (..., 3, 3)."""
    import numpy as np
    q = np.asarray(g.quats, np.float64)
    q = q / np.maximum(np.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
    w, x, y, z = np.moveaxis(q, -1, 0)
    R = np.stack([
        np.stack([1 - 2 * (y * y + z * z), 2 * (x * y - w * z),
                  2 * (x * z + w * y)], -1),
        np.stack([2 * (x * y + w * z), 1 - 2 * (x * x + z * z),
                  2 * (y * z - w * x)], -1),
        np.stack([2 * (x * z - w * y), 2 * (y * z + w * x),
                  1 - 2 * (x * x + y * y)], -1)], -2)
    s = np.exp(np.asarray(g.log_scales, np.float64))
    s = s / s.max(-1, keepdims=True)
    RS = R * s[..., None, :]
    return RS @ np.swapaxes(RS, -1, -2)


def _param_diff(a, b):
    """-> {field: (max |a - b|, fraction of elements above PARAM_ATOL)}
    over the trainables, with ``quats`` replaced by ``covariance``."""
    import numpy as np
    pairs = {k: (v, getattr(b, k)) for k, v in a.trainable().items()
             if k != "quats"}
    pairs["covariance"] = (_unit_covariance(a), _unit_covariance(b))
    out = {}
    for k, (u, v) in pairs.items():
        d = np.abs(np.asarray(u, np.float64) - np.asarray(v, np.float64))
        out[k] = (float(d.max()), float((d > PARAM_ATOL).mean()))
    return out


def four_chips(size: Size) -> dict:
    """2x2 all-gather and 2x2 exchange vs a 1x1 mesh; raises on failure."""
    import dataclasses as dc

    import jax
    import numpy as np

    from repro.core.distributed import fit_partitions
    from repro.launch import train
    from repro.launch.mesh import make_mesh

    if len(jax.devices()) != 4:
        raise SystemExit(f"--four-chips needs 4 devices, have "
                         f"{len(jax.devices())}")
    timer = CompileTimer()
    t0 = time.perf_counter()
    args = train.parse_args(list(size.train))
    cfg, mesh22, sc = train.setup_gs(args)
    mesh11 = make_mesh((1, 1), ("part", "view"), devices=jax.devices()[:1])
    log(f"prep: {args.parts} partitions x "
        f"{sc.g.means.shape[1]:,} splat slots, {args.views} views at "
        f"{args.resolution}x{args.resolution}, "
        f"{time.perf_counter() - t0:.1f}s")

    def fit(mesh, c):
        t = time.perf_counter()
        g, _, losses = fit_partitions(
            sc.g, sc.cams, sc.gts, sc.masks, c, mesh=mesh, steps=args.steps,
            extent=sc.extent, key=jax.random.PRNGKey(args.seed),
            grid=sc.grid, impl=c.impl)
        shape = "x".join(map(str, mesh.devices.shape))
        table = "exchange" if c.exchange else "all-gather"
        log(f"{shape} {table}: losses {[round(x, 6) for x in losses]} "
            f"({time.perf_counter() - t:.1f}s)")
        if shape == "2x2":
            mem = [d.memory_stats() or {} for d in jax.devices()]
            log("per-device bytes in use: "
                f"{[m.get('bytes_in_use') for m in mem]}, peak "
                f"{[m.get('peak_bytes_in_use') for m in mem]}")
            held = {s.device.id for leaf in jax.tree.leaves(g)
                    for s in leaf.addressable_shards}
            if len(held) != 4:
                raise AssertionError(f"2x2 state lives on devices {held}")
        return jax.device_get(g), losses

    ref_g, ref_l = fit(mesh11, cfg)
    report = {}
    for exchange in (False, True):
        c = dc.replace(cfg, exchange=exchange)
        g, losses = fit(mesh22, c)
        name = "exchange" if exchange else "all-gather"
        np.testing.assert_allclose(losses, ref_l, rtol=LOSS_RTOL,
                                   err_msg=f"2x2 {name} loss vs 1x1")
        diff = _param_diff(g, ref_g)
        log(f"2x2 {name} vs 1x1 params (max|diff|, frac > {PARAM_ATOL}): "
            + ", ".join(f"{k} {m:.3g}/{f:.2g}" for k, (m, f) in diff.items()))
        bad = {k: f for k, (_, f) in diff.items() if f > PARAM_MAX_FRAC}
        if bad:
            raise AssertionError(f"2x2 {name}: fields {bad} exceed "
                                 f"{PARAM_MAX_FRAC} off by > {PARAM_ATOL}")
        report[name] = {"losses": losses, "params": diff}
    log(f"compiles: {timer.n} backend compiles, {timer.seconds:.1f}s; "
        f"phase {time.perf_counter() - t0:.1f}s (smoke timing)")
    return report


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the 2x2-vs-1x1 mesh phase (4 chips)")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"chip_smoke.py: no src/repro next to {__file__}; run it "
                 "from a checkout of the repository")
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    from repro.launch.device import enable_compile_cache, require_tpu

    dev = require_tpu("chip_smoke.py")
    log(f"device: {dev.device_kind} x {len(jax.devices())} "
        f"({dev.platform}), jax {jax.__version__}, compile cache "
        f"{enable_compile_cache()}")
    t0 = time.perf_counter()
    if args.four_chips:
        four_chips(FULL4)
    else:
        kernels("pallas")
        one_chip(FULL)
    log(f"total {time.perf_counter() - t0:.1f}s (smoke timing)")
    print(json.dumps({"ok": True, "device": _device_line()}), flush=True)


if __name__ == "__main__":
    main()

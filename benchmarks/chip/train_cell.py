"""Training cells: steady minibatch steps through ``fit_partitions``.

Set-up makes the scene, renders the ground-truth images and coverage masks
with the plain reference, builds the initial splats on the device, and
enters ``repro.core.distributed.fit_partitions`` — the entry users call.
The benchmark wraps each train step that ``fit_partitions`` builds (it
replaces ``make_gs_train_step`` for the call, as a profiler hook would), so
one object is driven throughout: the first ``check_steps`` steps are
set-up (the first compiles or loads the step), their losses, the Adam state
after step 1 and the parameters after the last of them are kept, and the
window starts at the next step and ends at the first step boundary after
``--seconds``.  ``fit_partitions`` reads the loss every step, so each step
is synchronised with the device.

After the window the program's state is freed and the reference trains the
same splats on the same views for ``check_steps`` steps; see ``compare``.
"""

from __future__ import annotations

import gc
import time

import numpy as np

import reference as ref
import scene
import work
from harness import Check, span


class StopWindow(Exception):
    """Raised from the wrapped step once the window is spent."""


def grid_of(cfg) -> ref.Grid:
    th, tw = cfg["tile"]
    return ref.Grid(cfg["resolution"], cfg["resolution"], th, tw)


def _inputs(cfg, seed, cache):
    """Host inputs of one seed: partition rows padded to the capacity."""
    sc = scene.make_scene(cfg, seed, cache=cache)
    parts = scene.partition(sc, cfg["partitions"], cfg["ghost_frac"])
    cap = int(cfg["capacity"])
    n = [len(p.points) for p in parts]
    if max(n) > cap:
        raise ValueError(f"partition sizes {n} exceed capacity {cap}")
    P = len(parts)
    pts = np.zeros((P, cap, 3), np.float32)
    col = np.full((P, cap, 3), 0.5, np.float32)
    owner = np.zeros((P, cap), np.int32)
    scale = np.zeros((P,), np.float32)
    for i, p in enumerate(parts):
        pts[i, :n[i]] = p.points
        col[i, :n[i]] = p.colors
        owner[i] = i
        owner[i, :n[i]] = p.owner
        bbox = p.points.max(0) - p.points.min(0)
        scale[i] = (max(float(np.prod(bbox)), 1e-12) / n[i]) ** (1.0 / 3.0)
    views = scene.orbital_rig(cfg["n_views"], sc.center, sc.rig_radius)
    return sc, np.asarray(n), pts, col, owner, scale, views


def _splats(pts, col, n, scale, opacity):
    """Per-partition trainable dicts on the device (one jitted call):
    isotropic splats at the points, identity rotations, the given opacity
    and the points' colors; rows past ``n`` inactive."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def build(pts, col, n, scale):
        P, cap = pts.shape[:2]
        c = jnp.clip(col, 1e-4, 1 - 1e-4)
        tr = {"means": pts,
              "log_scales": jnp.broadcast_to(jnp.log(scale)[:, None, None],
                                             (P, cap, 3)),
              "quats": jnp.broadcast_to(
                  jnp.asarray([1.0, 0.0, 0.0, 0.0], jnp.float32),
                  (P, cap, 4)),
              "opacity_logit": jnp.full((P, cap),
                                        np.log(opacity / (1 - opacity)),
                                        jnp.float32),
              "colors": jnp.log(c / (1 - c))}
        active = jnp.arange(cap)[None, :] < n[:, None]
        return tr, active
    return build(pts, col, jnp.asarray(n), jnp.asarray(scale))


def _part(tree, p):
    return {k: v[p] for k, v in tree.items()}


def render_gt(cfg, pts, col, n, scale, views, f):
    """Ground truth of each partition's own (+ghost) points, rendered by
    the reference as near-opaque splats over a black background, and the
    training masks (coverage above 1/255, dilated twice by 3x3) ->
    host (gt_tiles (P, V, T, 3, th, tw), mask_tiles (P, V, T, th, tw))."""
    import jax
    import jax.numpy as jnp

    grid = grid_of(cfg)
    K = cfg["program"]["K"]
    tr, active = _splats(pts, col, n, scale, cfg["gt_opacity"])
    need = jax.jit(lambda t, a, v: ref.needed_slots(
        ref.project(t, a, v, f, grid), grid))
    renders = {}
    gts, masks = [], []
    for p in range(pts.shape[0]):
        tp, ap = _part(tr, p), active[p]
        slots = ref.slots_for(max(int(need(tp, ap, jnp.asarray(v)))
                                  for v in views))
        if slots not in renders:
            renders[slots] = _gt_view(grid, K, slots, float(f))
        g_p, m_p = [], []
        for v in views:
            tiles, mask = renders[slots](tp, ap, jnp.asarray(v))
            g_p.append(tiles)
            m_p.append(mask)
        gts.append(np.asarray(jnp.stack(g_p)))
        masks.append(np.asarray(jnp.stack(m_p)))
    return np.stack(gts), np.stack(masks)


def _gt_view(grid, K, slots, f):
    import jax

    @jax.jit
    def one(tr, active, view):
        tiles = ref.render_tiles(tr, active, view, f, grid, K, slots)
        cov = ref.untile(tiles[:, 3:], grid)[..., 0]
        mask = ref.dilate(cov > ref.ALPHA_MIN, 2)
        return tiles[:, :3], ref.to_tiles(mask[..., None], grid)[:, 0]
    return one


def program_cfg(cfg, overrides):
    """The configuration's ``program`` block, as it stands, as the
    program's ``GSTrainCfg`` (lists as tuples) on the configuration's tile,
    with a control run's overrides."""
    from repro.core.train import GSTrainCfg
    th, tw = cfg["tile"]
    p = {**cfg["program"], **overrides}
    return GSTrainCfg(tile_h=th, tile_w=tw,
                      **{k: tuple(v) if isinstance(v, list) else v
                         for k, v in p.items()})


class StepDriver:
    """Stands in for ``make_gs_train_step`` during ``fit_partitions``:
    builds the program's step and wraps it (see module docstring)."""

    def __init__(self, make, ctx, n_check: int, on_window_start,
                 on_window_end):
        self.make_orig = make
        self.ctx = ctx
        self.n_check = n_check
        self.i = 0
        self.built = 0
        self.check_losses = []
        self.dropped = 0
        self.m1 = self.theta = None
        self.state = None
        self.t_w0 = self.t_w1 = None
        self.steps = 0
        self.loop = None
        self.on_start, self.on_end = on_window_start, on_window_end

    def make(self, *a, **k):
        import jax
        fn = self.make_orig(*a, **k)
        self.built += 1

        def call(g, opt, batch):
            i = self.i
            now = time.perf_counter()
            if i == self.n_check:
                self.on_start()
                self.t_w0 = time.perf_counter()
            elif i > self.n_check and now - self.t_w0 >= self.ctx.seconds:
                self.t_w1 = now
                self.steps = i - self.n_check
                self.loop.__exit__(None, None, None)
                self.loop = None
                self.on_end()
                raise StopWindow
            if self.loop is not None:       # the program's own host loop
                self.loop.__exit__(None, None, None)
            with span("bench.step"):
                out = fn(g, opt, batch)
            self.loop = jax.profiler.TraceAnnotation("bench.fit_loop")
            self.loop.__enter__()
            self.state = out[:2]
            if i < self.n_check:
                self.check_losses.append(float(out[2]))
                self.dropped += int(np.asarray(out[3]["tiles"]))
                if i == 0:
                    self.m1 = jax.device_get(out[1].m)
                if i == self.n_check - 1:
                    self.theta = jax.device_get(out[0].trainable())
            self.i += 1
            return out
        return call


def run(ctx, *, cache=scene.CACHE) -> dict:
    """-> {"metrics": {...}, "checks": [...], "attempted", "failed",
    "trace_run": {...} for the per-layer readers}."""
    import jax
    import jax.numpy as jnp

    import repro.core.distributed as dist
    from repro.core.cameras import Camera
    from repro.core.gaussians import Gaussians
    from repro.core.tiling import TileGrid
    from repro.launch.mesh import make_mesh

    cfg, traffic = ctx.cfg, ctx.traffic
    grid = grid_of(cfg)
    f = scene.focal(cfg["resolution"])
    n_check = int(traffic["check_steps"])
    vb = cfg["program"]["view_batch"]

    t = time.perf_counter()
    with span("setup.prep"):
        sc, n, pts, col, owner, scale, views = _inputs(cfg, ctx.seed, cache)
    ctx.setup_parts["prep_s"] = time.perf_counter() - t

    t = time.perf_counter()
    with span("setup.gt"):
        gt_tiles, mask_tiles = render_gt(cfg, pts, col, n, scale, views, f)
        gts = np.asarray(jax.jit(jax.vmap(jax.vmap(
            lambda x: ref.untile(x, grid))))(jnp.asarray(gt_tiles)))
        masks = np.asarray(jax.jit(jax.vmap(jax.vmap(
            lambda x: ref.untile(x[:, None], grid)[..., 0])))(
                jnp.asarray(mask_tiles)))
    ctx.setup_parts["gt_s"] = time.perf_counter() - t

    t = time.perf_counter()
    pcfg = program_cfg(cfg, ctx.overrides)
    tr0, active0 = _splats(pts, col, n, scale, cfg["init_opacity"])
    g0 = Gaussians(**tr0, active=active0, owner=jnp.asarray(owner))
    cams = Camera(view=jnp.asarray(views),
                  fx=jnp.full((len(views),), f, jnp.float32),
                  fy=jnp.full((len(views),), f, jnp.float32),
                  width=grid.width, height=grid.height)
    pgrid = TileGrid(grid.width, grid.height, grid.tile_h, grid.tile_w)
    mesh = make_mesh(tuple(cfg["mesh"]), ("part", "view"),
                     devices=jax.devices()[:int(np.prod(cfg["mesh"]))])
    sched = pcfg.tier_schedule()
    ctx.setup_parts["state_s"] = time.perf_counter() - t

    driver = StepDriver(dist.make_gs_train_step, ctx, n_check,
                        on_window_start=lambda: _window_start(ctx),
                        on_window_end=lambda: _window_end(ctx))
    dist.make_gs_train_step = driver.make
    t_fit = time.perf_counter()
    try:
        dist.fit_partitions(
            g0, cams, gts, masks, pcfg, mesh=mesh, steps=10 ** 9,
            extent=sc.extent, key=jax.random.PRNGKey(0), grid=pgrid,
            schedule=sched, impl=pcfg.impl, **traffic.get("fit", {}))
    except StopWindow:
        pass
    finally:
        dist.make_gs_train_step = driver.make_orig
    ctx.setup_parts["fit_setup_s"] = driver.t_w0 - t_fit
    ctx.notes["setup_end"] = driver.t_w0
    ctx.notes["tier_schedule"] = repr(sched)
    ctx.notes["steps_built"] = driver.built
    ctx.notes["window_steps"] = driver.steps
    ctx.notes["check_losses"] = driver.check_losses
    window_s = driver.t_w1 - driver.t_w0
    step_s = window_s / max(driver.steps, 1)
    from harness import device_line
    dev = device_line(int(np.prod(cfg["mesh"])))

    # free the program's state before the reference runs
    driver.state = None
    del g0, tr0, gts, masks
    gc.collect()

    t = time.perf_counter()
    checks, numbers = compare(cfg, pcfg, driver, views, f, pts, col, n,
                              scale, gt_tiles, mask_tiles, sc.extent)
    ctx.notes["reference_s"] = time.perf_counter() - t
    ctx.notes.update(numbers)

    out = {"metrics": {"train_step_s": step_s}, "checks": checks,
           "attempted": driver.steps + n_check, "failed": 0, "device": dev,
           "window_s": window_s}
    if ctx.trace:
        out["work"] = train_work(cfg, driver, views, f, vb, n)
    return out


def _window_start(ctx):
    tr = ctx.notes.get("trace_hooks")
    if tr:
        tr[0]()


def _window_end(ctx):
    tr = ctx.notes.get("trace_hooks")
    if tr:
        tr[1]()


def norms(tree):
    """{leaf: float64 2-norm over every element}."""
    return {k: float(np.linalg.norm(np.asarray(v, np.float64).ravel()))
            for k, v in tree.items()}


def gap(p: dict, r: dict, leaves) -> float:
    """Worst leaf's |norm_p - norm_r| over max(norm_r, median leaf norm)."""
    med = float(np.median([r[k] for k in leaves]))
    return max(abs(p[k] - r[k]) / max(r[k], med, 1e-30) for k in leaves)


def compare(cfg, pcfg, driver, views, f, pts, col, n, scale, gt_tiles,
            mask_tiles, extent):
    """Reference steps vs the program's first ``n_check`` steps.

    Numbers compared: the worst relative loss gap over the steps; the worst
    leaf's gap between gradient norms of step 1 (the program's gradient is
    its first Adam moment over 1 - b1); the worst leaf's gap between the
    norms of the parameters' change over the steps, leaving out leaves whose
    reference gradient norm is under a thousandth of the median leaf's
    (the rotations of isotropic splats, whose gradient is rounding); and
    the tiles the tiered rasterizer dropped in those steps (exact: 0)."""
    import jax
    import jax.numpy as jnp

    grid = grid_of(cfg)
    a = cfg["program"]
    lrs = {"means": a["lr_means"] * extent, "log_scales": a["lr_scales"],
           "quats": a["lr_quats"], "opacity_logit": a["lr_opacity"],
           "colors": a["lr_colors"]}
    n_check = len(driver.check_losses)
    vb, V = a["view_batch"], len(views)
    P = pts.shape[0]
    tr, active = _splats(pts, col, n, scale, cfg["init_opacity"])
    theta0 = jax.device_get(tr)
    trs = [_part(tr, p) for p in range(P)]
    acts = [active[p] for p in range(P)]
    m = [jax.tree.map(jnp.zeros_like, t) for t in trs]
    v = [jax.tree.map(jnp.zeros_like, t) for t in trs]
    need = jax.jit(lambda t, a_, vw: ref.needed_slots(
        ref.project(t, a_, vw, f, grid), grid))
    losses_r, grads1 = [], None
    step_fns = {}
    with jax.default_matmul_precision("highest"):
        for s in range(n_check):
            vi = (s * vb + np.arange(vb)) % V
            slots = ref.slots_for(max(int(need(trs[p], acts[p],
                                               jnp.asarray(views[i])))
                                      for p in range(P) for i in vi))
            if slots not in step_fns:
                step_fns[slots] = jax.jit(jax.value_and_grad(ref.make_loss(
                    grid, a["K"], slots, a["lambda_dssim"])))
            loss, grads = step_fns[slots](
                trs, acts, jnp.asarray(views[vi]), f,
                jnp.asarray(gt_tiles[:, vi]), jnp.asarray(mask_tiles[:, vi]))
            losses_r.append(float(loss))
            if s == 0:
                grads1 = {k: np.stack([np.asarray(g[k]) for g in grads])
                          for k in ref.TRAINABLE}
            upd = [ref.adam(trs[p], m[p], v[p], grads[p], s + 1, lrs,
                            a["b1"], a["b2"], a["eps"]) for p in range(P)]
            trs = [u[0] for u in upd]
            m = [u[1] for u in upd]
            v = [u[2] for u in upd]
    theta_r = {k: np.stack([np.asarray(t[k]) for t in trs])
               for k in ref.TRAINABLE}

    lp = np.asarray(driver.check_losses, np.float64)
    lr = np.asarray(losses_r, np.float64)
    loss_gap = float(np.max(np.abs(lp - lr) / np.abs(lr)))
    g_p = norms({k: np.asarray(driver.m1[k], np.float64) / (1 - a["b1"])
                 for k in ref.TRAINABLE})
    g_r = norms(grads1)
    grad_gap = gap(g_p, g_r, ref.TRAINABLE)
    med = float(np.median(list(g_r.values())))
    moved = [k for k in ref.TRAINABLE if g_r[k] >= 1e-3 * med]
    d_p = norms({k: np.asarray(driver.theta[k], np.float64)
                 - np.asarray(theta0[k], np.float64) for k in moved})
    d_r = norms({k: theta_r[k].astype(np.float64)
                 - np.asarray(theta0[k], np.float64) for k in moved})
    change_gap = gap(d_p, d_r, moved)
    lim = cfg["limits"]
    checks = [Check("loss_gap", loss_gap, lim["loss_gap"]),
              Check("grad_gap", grad_gap, lim["grad_gap"]),
              Check("change_gap", change_gap, lim["change_gap"]),
              Check("dropped_tiles", float(driver.dropped), 0.0)]
    numbers = {"losses_program": lp.tolist(), "losses_reference": lr.tolist(),
               "grad_norms_program": g_p, "grad_norms_reference": g_r,
               "change_norms_program": d_p, "change_norms_reference": d_r,
               "leaves_compared_for_change": moved}
    return checks, numbers


def train_work(cfg, driver, views, f, vb, n):
    """Per-window work counts (``work.py``) from the parameters the window
    started with and the views each window step trained on."""
    import jax.numpy as jnp

    grid = grid_of(cfg)
    theta = driver.theta
    P = theta["means"].shape[0]
    V = len(views)
    active = [jnp.arange(theta["means"].shape[1]) < n[p] for p in range(P)]
    tiles_live = np.zeros((V,), np.float64)   # sum over tiles of min(n_t, K)
    count = work.CappedCounts(grid, cfg["program"]["K"])
    for p in range(P):
        tp = {k: jnp.asarray(vv[p]) for k, vv in theta.items()}
        for i in range(V):
            tiles_live[i] += count(tp, active[p], jnp.asarray(views[i]), f)
    pairs = tiles_live * grid.tile_h * grid.tile_w
    first = driver.n_check
    steps = [(s * vb + np.arange(vb)) % V
             for s in range(first, first + driver.steps)]
    win_pairs = float(sum(pairs[vi].sum() for vi in steps))
    win_splat_refs = float(sum(tiles_live[vi].sum() for vi in steps))
    n_tiles_px = grid.n_tiles * grid.tile_h * grid.tile_w
    live = float(np.sum(n))
    return work.train_window(
        steps=driver.steps, pairs=win_pairs, splat_refs=win_splat_refs,
        tiles=driver.steps * vb * P * grid.n_tiles,
        pixels=driver.steps * vb * P * n_tiles_px,
        splat_views=driver.steps * vb * live, params=14 * live)

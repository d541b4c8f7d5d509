"""Serving cells: open-loop render requests through ``GSRenderServer``.

Set-up makes the scene and, in one jitted call from the seed, the served
model: one splat per isosurface point with seeded anisotropic scales,
rotations, opacities and colours (untrained stand-ins of a trained model,
with its count and shapes).  It builds the server (LOD ladder included),
runs one dispatch of every batch size on every LOD rung so that each program
is compiled or loaded, clears the cache, and primes it where the mix says.

The window is open loop (``open_loop.py``): at each tick the harness
submits every request now due, then flushes; each request is timed from
when it was due to when its image is on the host.  The server has no
transport of its own, so this loop is its client.

After the window the server is freed and the reference renders a seeded
sample of the requests from its own copy of the model, with the
configuration's pose buckets, LOD rule and shedding rule; see ``compare``.
The sample always holds a far request, one that must be a cache hit and
one served in each dispatch slot the window used (``draw_sample``).
"""

from __future__ import annotations

import gc
import time

import numpy as np

import open_loop
import reference as ref
import scene
import work
from harness import Check, device_line, span


def grid_of(cfg) -> ref.Grid:
    th, tw = cfg["tile"]
    return ref.Grid(cfg["resolution"], cfg["resolution"], th, tw)


def model(cfg, sc: scene.Scene, seed: int):
    """Trainable dict of the served splats, on the device, one jitted
    call: log-scales about the isotropic spacing of the cloud, random
    rotations, opacities and colour offsets, all from ``seed``."""
    import jax
    import jax.numpy as jnp

    m = cfg["model"]
    n = len(sc.points)
    bbox = sc.points.max(0) - sc.points.min(0)
    s0 = (float(np.prod(bbox)) / n) ** (1.0 / 3.0)
    k = int(np.random.default_rng([seed, 11]).integers(2 ** 31))

    @jax.jit
    def build(pts, col, key):
        k1, k2, k3, k4 = jax.random.split(key, 4)
        c = jnp.clip(col, 1e-4, 1 - 1e-4)
        return {
            "means": pts,
            "log_scales": jnp.log(s0) + m["log_scale_std"]
            * jax.random.normal(k1, (n, 3)),
            "quats": jax.random.normal(k2, (n, 4)),
            "opacity_logit": m["opacity_logit_mean"] + m["opacity_logit_std"]
            * jax.random.normal(k3, (n,)),
            "colors": jnp.log(c / (1 - c)) + m["color_logit_std"]
            * jax.random.normal(k4, (n, 3))}
    return build(jnp.asarray(sc.points), jnp.asarray(sc.colors),
                 jax.random.PRNGKey(k))


def server_cfg(cfg, overrides):
    """The configuration's ``server`` block, as it stands, as the program's
    ``ServeCfg`` (lists as tuples), with a control run's overrides."""
    from repro.core.serving import ServeCfg
    s = {**cfg["server"], **overrides}
    return ServeCfg(**{k: tuple(v) if isinstance(v, list) else v
                       for k, v in s.items()})


def _camera(view, f, grid):
    import jax.numpy as jnp

    from repro.core.cameras import Camera
    return Camera(jnp.asarray(view), jnp.float32(f), jnp.float32(f),
                  grid.width, grid.height)


def setup(ctx, *, cache=scene.CACHE) -> dict:
    """Scene, pose trace, model, server, warm-up and priming."""
    import jax.numpy as jnp

    from repro.core.gaussians import Gaussians
    from repro.core.serving import GSRenderServer
    from repro.core.tiling import TileGrid

    cfg, traffic = ctx.cfg, ctx.traffic
    grid = grid_of(cfg)
    f = scene.focal(cfg["resolution"])
    scfg = server_cfg(cfg, ctx.overrides)

    t = time.perf_counter()
    with span("setup.prep"):
        sc = scene.make_scene(cfg, ctx.seed, cache=cache)
        reqs, prime = open_loop.make(traffic, center=sc.center,
                                     rig_radius=sc.rig_radius,
                                     seconds=ctx.seconds, seed=ctx.seed)
        tr = model(cfg, sc, ctx.seed)
        n = len(sc.points)
        g = Gaussians(**tr, active=jnp.ones((n,), bool),
                      owner=jnp.zeros((n,), jnp.int32))
    ctx.setup_parts["prep_s"] = time.perf_counter() - t

    t = time.perf_counter()
    with span("setup.server"):
        pgrid = TileGrid(grid.width, grid.height, grid.tile_h, grid.tile_w)
        server = GSRenderServer(g, pgrid, scfg)
        del g, tr
    ctx.setup_parts["server_s"] = time.perf_counter() - t

    t = time.perf_counter()
    with span("setup.warm"):
        sizes = []
        b = 1
        while b <= scfg.max_batch:
            sizes.append(b)
            b *= 2
        # every batch size on every LOD rung, from poses off the mixes'
        # paths (near the pole), each rung at a distance that selects it
        radii = [sc.rig_radius] + [1.5 * d for d in server.lod_dists]
        for r in radii:
            for k, b in enumerate(sizes):
                for i in range(b):
                    az = 2 * np.pi * (i + 0.5) / b + 0.1 * k
                    server.submit(_camera(scene.pose(sc.center, r, az,
                                                     np.radians(80.0)),
                                          f, grid))
                server.flush()
        server.clear_cache()
        for i in range(0, len(prime), scfg.max_batch):
            for v, _ in prime[i:i + scfg.max_batch]:
                server.submit(_camera(v, f, grid))
            server.flush()
    ctx.setup_parts["warm_s"] = time.perf_counter() - t
    return {"server": server, "scene": sc, "grid": grid, "f": f,
            "scfg": scfg, "reqs": reqs}


def window(ctx, st: dict, reqs) -> dict:
    """The open loop over ``reqs``; keeps every served image, with its
    rung, K, cache hit, shedding and slot in its dispatch."""
    from repro.core.serving import QueueFullError

    server, f, grid, scfg = st["server"], st["f"], st["grid"], st["scfg"]
    kept = {}
    tel0 = server.telemetry()
    n_req = len(reqs)
    done = np.full(n_req, np.nan)
    late = np.zeros(n_req)
    sub = np.full(n_req, np.nan)
    shed_expected = np.zeros(n_req, bool)
    rid_of = {}
    failed = flushes = 0
    flush_s = 0.0
    shed_at = scfg.shed_at if scfg.shed_at is not None \
        else max(1, scfg.queue_cap // 2)
    hooks = ctx.notes.get("trace_hooks")
    if hooks:
        hooks[0]()
    t_w0 = time.perf_counter()
    ctx.notes["setup_end"] = t_w0
    i = pending = 0
    while i < n_req or pending:
        now = time.perf_counter() - t_w0
        with span("bench.submit"):
            while i < n_req and reqs[i].arrival_s <= now:
                late[i] = now - reqs[i].arrival_s
                sub[i] = now
                shed_expected[i] = pending >= shed_at
                try:
                    rid = server.submit(_camera(reqs[i].view, f, grid))
                    rid_of[rid] = i
                    pending += 1
                except QueueFullError:
                    failed += 1
                i += 1
        if pending:
            t0 = time.perf_counter()
            with span("bench.flush"):
                results = server.flush()
            t_done = time.perf_counter() - t_w0
            flushes += 1
            flush_s += time.perf_counter() - t0
            for r, slot in zip(results, _slots(results, scfg.max_batch)):
                j = rid_of.pop(r.request_id)
                done[j] = t_done
                kept[j] = (r.rgb, r.rung, r.K, r.cache_hit, r.shed, slot)
            pending = 0
        elif i < n_req:
            with span("bench.wait"):
                time.sleep(max(0.0, reqs[i].arrival_s
                               - (time.perf_counter() - t_w0)))
    t_end = time.perf_counter() - t_w0
    if hooks:
        hooks[1]()
    tel = {k: v - tel0.get(k, 0) for k, v in server.telemetry().items()}
    arrivals = np.asarray([r.arrival_s for r in reqs])
    lat = done - arrivals
    lat_all = np.where(np.isnan(lat), np.inf, lat)
    completed = int(np.sum(~np.isnan(done)))
    window_s = max(ctx.seconds,
                   float(np.nanmax(done)) if completed else t_end)
    return {
        "serve_p95_ms": float(np.percentile(lat_all, 95)) * 1e3,
        "serve_rps": completed / window_s, "window_s": window_s,
        "done": done, "submitted": sub,
        "kept": kept, "shed_expected": shed_expected, "failed": failed,
        "telemetry": tel,
        "notes": {
            "requests": n_req, "completed": completed, "failed": failed,
            "latency_ms": {f"p{q}": float(np.percentile(lat_all, q)) * 1e3
                           for q in (50, 75, 90, 95, 99)},
            "latency_ms_max": float(np.nanmax(lat)) * 1e3,
            "generator_late_ms_p50": float(np.percentile(late, 50)) * 1e3,
            "generator_late_ms_p95": float(np.percentile(late, 95)) * 1e3,
            "generator_late_ms_max": float(late.max()) * 1e3,
            "telemetry": tel, "window_end_s": t_end, "flushes": flushes,
            "flush_s": flush_s}}


def run(ctx, *, cache=scene.CACHE) -> dict:
    st = setup(ctx, cache=cache)
    reqs, scfg = st["reqs"], st["scfg"]
    w = window(ctx, st, reqs)
    ctx.notes.update(w["notes"])
    dev = device_line(1)

    ladder_k = st["server"].schedule.k_tiers
    sc, f = st["scene"], st["f"]
    st.clear()
    gc.collect()

    hits = expected_hits(reqs, f, scfg.pose_bins, scfg.cache_entries,
                         w["done"], w["submitted"])
    sample = draw_sample(reqs, w["kept"], hits,
                         int(ctx.traffic["check_requests"]), ctx.seed)
    kept = {j: w["kept"][j] for j in sample if j in w["kept"]}
    del w["kept"]
    ctx.notes["expected_hits"] = len(hits)
    t = time.perf_counter()
    checks, numbers = compare(ctx.cfg, sc, ctx.seed, reqs, sample, kept,
                              w["shed_expected"], ladder_k, f)
    ctx.notes["reference_s"] = time.perf_counter() - t
    ctx.notes.update(numbers)
    out = {"metrics": {"serve_p95_ms": w["serve_p95_ms"],
                       "serve_rps": w["serve_rps"]},
           "checks": checks, "attempted": len(reqs), "failed": w["failed"],
           "device": dev, "window_s": w["window_s"], "telemetry": w["telemetry"]}
    if ctx.trace:
        out["work"] = serve_work(ctx.cfg, sc, ctx.seed, reqs, w["done"], f)
    return out


def _slots(results, max_batch: int):
    """Each result's slot in its dispatch: the server groups a flush's
    requests by (rung, K) in submission order and cuts each group into
    batches of ``max_batch``."""
    seen = {}
    out = []
    for r in results:
        k = (r.rung, r.K)
        out.append(seen.get(k, 0) % max_batch)
        seen[k] = seen.get(k, 0) + 1
    return out


def expected_hits(reqs, f, bins: float, entries: int, done, submitted):
    """Requests that must be cache hits: the last earlier request of the
    same pose bucket was served before this one was submitted, and fewer
    than ``entries`` other buckets were asked for in between."""
    keys = [canonical(r.view, f, bins)[0].tobytes() for r in reqs]
    out = []
    for j in range(len(reqs)):
        for i in range(j - 1, -1, -1):
            if keys[i] == keys[j]:
                if done[i] <= submitted[j] and \
                        len(set(keys[i + 1:j])) < entries:
                    out.append(j)
                break
    return out


def draw_sample(reqs, kept, hits, count: int, seed: int):
    """Seeded sample of ``count`` request indices that holds a far request,
    a request that must hit the cache, and a request served in each
    dispatch slot, wherever the window had one."""
    rng = np.random.default_rng([seed, 13])
    served = sorted(kept)
    groups = [[j for j in served if reqs[j].far], list(hits)]
    for slot in sorted({kept[j][5] for j in served}):
        groups.append([j for j in served if kept[j][5] == slot])
    must = []
    for g in groups:
        if g and not set(g) & set(must):
            must.append(int(g[rng.integers(len(g))]))
    rest = [int(j) for j in rng.permutation(len(reqs)) if j not in must]
    return set((must + rest)[:max(count, len(must))])


# ---------------------------------------------------------------------------
# Reference side
# ---------------------------------------------------------------------------


def canonical(view, f, bins: float):
    """The pose-bucket lattice point a request is served at."""
    v = np.rint(np.asarray(view, np.float64) * bins) / bins
    fq = np.rint(np.float64(f) * (bins / 1024.0)) * (1024.0 / bins)
    return v.astype(np.float32), np.float32(fq)


def lod_keep(tr_host: dict, frac: float) -> np.ndarray:
    """The top ceil(frac n) splats by opacity x mean squared scale; ties
    by row."""
    alpha = 1.0 / (1.0 + np.exp(-np.asarray(tr_host["opacity_logit"],
                                            np.float64)))
    area = np.exp(2.0 * np.asarray(tr_host["log_scales"],
                                   np.float64)).mean(-1)
    n = len(alpha)
    k = min(n, int(np.ceil(frac * n)))
    keep = np.zeros(n, bool)
    keep[np.argsort(-(alpha * area), kind="stable")[:k]] = True
    return keep


def compare(cfg, sc, seed, reqs, sample, kept, shed_expected, ladder_k, f):
    """Reference renders of the sampled requests.

    Each is rendered at its canonical (bucket) pose, on the LOD rung its
    distance selects (beyond 4x the model's radius: the coarser rung), at
    the ladder's lowest K when the queue it met was past the shedding
    depth, over the white background.  Numbers compared: the largest
    mean absolute difference of one image over the sample, and the sampled
    requests that never came (exact: 0).  The largest single-pixel
    difference is reported, not compared: a 1 px change of a splat's
    integer radius, from float32 rounding of its eigenvalue, moves it in or
    out of a tile it grazes and shifts a few pixels by up to about 0.1."""
    import jax
    import jax.numpy as jnp

    s = cfg["server"]
    grid = grid_of(cfg)
    tr = model(cfg, sc, seed)
    host = jax.device_get(tr)
    means = np.asarray(host["means"], np.float64)
    center = 0.5 * (means.max(0) + means.min(0))
    radius = float(np.linalg.norm(means - center, axis=-1).max())
    dists = [radius * 4.0 * 2.0 ** i for i in range(len(s["lod_fracs"]) - 1)]
    actives = [jnp.asarray(lod_keep(host, fr)) for fr in s["lod_fracs"]]
    todo = []
    for j in sorted(sample):
        view, fq = canonical(reqs[j].view, f, s["pose_bins"])
        eye = -view[:3, :3].astype(np.float64).T @ view[:3, 3]
        rung = int(sum(np.linalg.norm(eye - center) > d for d in dists))
        K = int(ladder_k[0]) if shed_expected[j] else int(s["K"])
        todo.append((j, jnp.asarray(view), jnp.float32(fq), rung, K))
    need = jax.jit(lambda t, a, v, fo: ref.needed_slots(
        ref.project(t, a, v, fo, grid), grid))
    slots = ref.slots_for(max(int(need(tr, actives[r], v, fo))
                              for _, v, fo, r, _ in todo))
    render = jax.jit(lambda t, a, v, fo, K: ref.render_tiles(
        t, a, v, fo, grid, K, slots), static_argnums=4)
    worst_max = worst_mean = 0.0
    missing = 0
    per = []
    with jax.default_matmul_precision("highest"):
        for j, vj, fq, rung, K in todo:
            if j not in kept:
                missing += 1
                continue
            rgb, rung_p, k_p, hit, _, slot = kept[j]
            img = np.asarray(ref.untile(render(tr, actives[rung], vj, fq, K),
                                        grid))
            want = img[..., :3] + (1.0 - img[..., 3:]) * s["bg"]
            d = np.abs(np.asarray(rgb, np.float64) - want)
            worst_max = max(worst_max, float(d.max()))
            worst_mean = max(worst_mean, float(d.mean()))
            per.append({"request": int(j), "rung": rung, "served_rung":
                        int(rung_p), "K": K, "served_K": int(k_p),
                        "hit": bool(hit), "slot": int(slot),
                        "max": float(d.max()),
                        "mean": float(d.mean())})
    checks = [Check("img_mean_gap", worst_mean,
                    cfg["limits"]["img_mean_gap"]),
              Check("sampled_missing", float(missing), 0.0)]
    return checks, {"samples": per, "img_max_gap": worst_max}


def serve_work(cfg, sc, seed, reqs, done, f):
    """Forward compositing work of the served requests, from the model
    and each served request's canonical pose and rung (``work.py``)."""
    import jax
    import jax.numpy as jnp

    s = cfg["server"]
    grid = grid_of(cfg)
    tr = model(cfg, sc, seed)
    host = jax.device_get(tr)
    means = np.asarray(host["means"], np.float64)
    center = 0.5 * (means.max(0) + means.min(0))
    radius = float(np.linalg.norm(means - center, axis=-1).max())
    dists = [radius * 4.0 * 2.0 ** i for i in range(len(s["lod_fracs"]) - 1)]
    actives = [jnp.asarray(lod_keep(host, fr)) for fr in s["lod_fracs"]]
    count = work.CappedCounts(grid, s["K"])
    memo = {}
    refs = 0.0
    served = 0
    for j, r in enumerate(reqs):
        if np.isnan(done[j]):
            continue
        served += 1
        view, fq = canonical(r.view, f, s["pose_bins"])
        key = view.tobytes()
        if key not in memo:
            eye = -view[:3, :3].astype(np.float64).T @ view[:3, 3]
            rung = int(sum(np.linalg.norm(eye - center) > d for d in dists))
            memo[key] = count(tr, actives[rung], jnp.asarray(view), fq)
        refs += memo[key]
    px = grid.tile_h * grid.tile_w
    pixels = served * grid.n_tiles * px
    return {"raster_fwd": work.raster_fwd(refs * px, refs, pixels),
            "served": served}

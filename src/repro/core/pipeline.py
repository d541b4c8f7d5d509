"""End-to-end single-host pipeline for the paper's workflow (§II, Fig. 1):

  volume -> isosurface point cloud -> camera rig -> spatial partitioning
  (+ghost cells) -> per-partition GT renders + background masks ->
  independent per-partition training -> merge -> global evaluation.

This is the CPU-tractable mirror of the production path (launch/train.py +
core/distributed.py run the same stages sharded over the mesh); benchmarks
and the quality-ablation tests drive this module.
"""

from __future__ import annotations

import concurrent.futures
import dataclasses
import functools
import time
import warnings
from typing import List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.gs_datasets import GSDataset, get_gs_dataset
from repro.core import merge as merge_mod
from repro.core import metrics
from repro.core.cameras import Camera, orbital_rig, select
from repro.core.gaussians import Gaussians, from_points
from repro.core.masking import dilate_mask
from repro.core.partition import PartitionData, partition_points
from repro.core.render import (occupancy_probe_jit, render_batch,
                              resolve_assignment)
from repro.core.tiling import (DEFAULT_ASSIGN_IMPL, TierSchedule, TileGrid,
                               auto_tier_caps)
from repro.core.train import GSTrainCfg, fit_partition
from repro.data.isosurface import point_cloud_for


@dataclasses.dataclass
class PipelineCfg:
    dataset: str = "sphere_shell"
    tier: str = "cpu"
    n_parts: int = 2
    resolution: int = 64
    steps: int = 200
    K: int = 48
    use_ghost: bool = True          # ablation switches (Fig. 2/4)
    use_mask: bool = True
    densify_every: int = 0
    train: GSTrainCfg = dataclasses.field(default_factory=GSTrainCfg)
    n_views: Optional[int] = None   # override dataset default
    seed: int = 0


@dataclasses.dataclass
class PipelineResult:
    merged: Gaussians
    parts: List[Gaussians]
    psnr: float
    ssim: float
    grad_sim: float
    train_seconds: List[float]
    n_gaussians: int
    gt_images: np.ndarray
    renders: np.ndarray
    # metrics restricted to partition-boundary pixels — where the paper's
    # Fig. 2 artifacts (gaps/streaks) live; the global numbers dilute them
    boundary_psnr: float = float("nan")
    boundary_ssim: float = float("nan")
    boundary_frac: float = 0.0


def build_scene(ds: GSDataset, seed: int = 0, t: float = 0.0):
    """``t`` extracts the time-evolved field's isosurface (timeseries
    driver); ``t=0`` is bit-identical to the static scene."""
    points, colors = point_cloud_for(ds.volume, ds.n_points, seed=seed, t=t)
    extent = float(np.linalg.norm(points.max(0) - points.min(0)))
    return points, colors, extent


def gt_gaussians(points, colors, *, owner_id: int = 0) -> Gaussians:
    """Ground-truth splats straight from the point cloud (paper Fig. 4a:
    'ground truth image rendered directly from the point cloud')."""
    return from_points(jnp.asarray(points), jnp.asarray(colors),
                       owner_id=owner_id, opacity=0.95)


def init_partition_gaussians(pd: PartitionData, *,
                             capacity: Optional[int] = None,
                             opacity: float = 0.6) -> Gaussians:
    """Trainable splats for one partition's (owned + ghost) points.

    ``capacity`` reserves free slots for densification (padding slots carry
    the partition's own id so densified children merge-dedupe correctly).
    Shared by run_pipeline and the distributed CLI driver
    (launch/train.py --gs), which needs EQUAL capacities across partitions
    for the batched (P, N) mesh layout.
    """
    cap = capacity or len(pd.points)
    g0 = from_points(jnp.asarray(pd.points), jnp.asarray(pd.colors),
                     capacity=cap, opacity=opacity)
    return g0._replace(owner=jnp.concatenate([
        jnp.asarray(pd.owner),
        jnp.full((cap - len(pd.points),), pd.part_id, jnp.int32)]))


def coverage_masks(part_cov, *, threshold: float = 1.0 / 255.0,
                   dilation: int = 2) -> np.ndarray:
    """(V, H, W) coverage renders -> (V, H, W) bool training masks
    (thresholded + dilated; paper §II step 4)."""
    return np.stack([
        np.asarray(dilate_mask(jnp.asarray(c > threshold), dilation))
        for c in part_cov
    ])


@functools.lru_cache(maxsize=64)
def _render_batch_jit(grid: TileGrid, K: int, impl: str, bg: float,
                      coarse: Optional[int],
                      k_tiers: Optional[tuple] = None,
                      tier_caps: Optional[tuple] = None,
                      assign_impl: str = DEFAULT_ASSIGN_IMPL,
                      assign_budget: Optional[int] = None,
                      coarse_budget: Optional[int] = None):
    """Cached jitted render_batch: the seed's render_views rebuilt its jit
    closure per call, recompiling the renderer every time the pipeline
    rendered a new gaussian set (GT, per-partition GT, merged, boundary —
    4+2P compiles per run).  Keying on the static render config (incl. the
    tier schedule and caps — auto_tier_caps rounds caps so nearby scenes
    share an entry — and the assignment impl + EVERY static budget: two
    callers differing only in ``assign_budget`` or ``coarse_budget`` must
    never share a compiled fn, since the budget is baked into the traced
    graph) makes every same-shaped call after the first dispatch-only.
    ``tests/test_batched_render.py::test_render_batch_jit_cache_keys_distinct``
    pins the key."""
    return jax.jit(lambda gg, cc: render_batch(gg, cc, grid, K=K, impl=impl,
                                               bg=bg, coarse=coarse,
                                               coarse_budget=coarse_budget,
                                               k_tiers=k_tiers,
                                               tier_caps=tier_caps,
                                               assign_impl=assign_impl,
                                               assign_budget=assign_budget))


def render_views(g: Gaussians, cams: Camera, grid: TileGrid, *, K: int,
                 impl: str = "auto", bg: float = 1.0, batch: int = 8,
                 coarse: Optional[int] = None,
                 coarse_budget: Optional[int] = None,
                 k_tiers: Optional[tuple] = None,
                 tier_caps: Optional[tuple] = None,
                 schedule: Optional[TierSchedule] = None,
                 assign_impl: str = DEFAULT_ASSIGN_IMPL,
                 assign_budget: Optional[int] = None):
    """-> (V, H, W, 3) rgb + (V, H, W) coverage.

    View-batched: renders ``batch`` views per dispatch through
    ``render_batch`` (one flattened kernel launch per chunk) instead of the
    former one-jit-call-per-view Python loop.  The tail chunk is padded by
    repeating the last view (then cropped) so every dispatch shares one
    traced shape.

    ``k_tiers`` enables occupancy-tiered rasterization; ``K`` is then
    ignored (both the render and the cap-sizing prepass assign at
    k_tiers[-1], since occupancy must be measured at the depth the render
    uses).  When ``tier_caps`` is None the caps are sized from an occupancy
    prepass of the FIRST chunk only (with slack), and the per-chunk
    overflow counter closes the loop: a later chunk that outgrows the caps
    is re-rendered with doubled caps (a bounded number of extra compiles)
    — so every returned image is exact without paying a full-rig prepass.
    Explicit ``tier_caps`` are never altered; if they drop tiles, a
    RuntimeWarning reports the overflow instead of silently returning
    background where geometry was.

    ``schedule=`` plugs a ``core.tiling.TierSchedule`` into the same loop
    (mutually exclusive with k_tiers/tier_caps): its active
    (k_tiers, tier_caps) drive the render — probed here on the first chunk
    when it has no caps yet — and overflow growth is written BACK via
    ``schedule.note_overflow``, so a caller alternating training and
    rendering keeps one consistent, telemetry-updated schedule.

    ``assign_impl``/``assign_budget`` pick the tile-assignment algorithm
    ("auto": sort-based on large grids, dense below the crossover; the
    occupancy probes run with the same impl as the render they size);
    ``coarse_budget`` pins the coarse pre-cull's per-superblock candidate
    budget (``coarse`` mode only — both budgets are part of the cached
    jit's key, so distinct budgets never share a compiled fn).
    When the sorted path is in play and no budget is given,
    ``render.resolve_assignment`` probes the WHOLE rig's concrete bbox
    counts to size the static per-splat budget (with slack, so the
    renders stay exact) — and demotes "auto" back to the dense sweep when
    the probed per-splat overlap is too fat for duplicate-and-sort to win
    (tiling.SORTED_BUDGET_RATIO).
    """
    assign_impl, assign_budget = resolve_assignment(
        g, cams, grid, assign_impl=assign_impl, assign_budget=assign_budget)
    if schedule is not None:
        if k_tiers is not None or tier_caps is not None:
            raise ValueError("pass either schedule= or explicit "
                             "k_tiers/tier_caps, not both")
        if schedule.tier_caps is None:
            vi0 = jnp.clip(jnp.arange(max(1, min(batch, cams.view.shape[0]))),
                           0, cams.view.shape[0] - 1)
            schedule.probe(occupancy_probe_jit(
                grid, schedule.kmax, coarse, assign_impl, assign_budget)(
                g, select(cams, vi0)))
        k_tiers, tier_caps = schedule.k_tiers, schedule.tier_caps
    V = cams.view.shape[0]
    batch = max(1, min(batch, V))
    auto_caps = k_tiers is not None and (tier_caps is None
                                         or schedule is not None)
    if k_tiers is not None:
        k_tiers = tuple(int(k) for k in k_tiers)
        K = k_tiers[-1]      # dead in tiered mode: pin the jit cache key
        if tier_caps is None:
            vi0 = jnp.clip(jnp.arange(batch), 0, V - 1)
            occ0 = occupancy_probe_jit(
                grid, k_tiers[-1], coarse, assign_impl, assign_budget)(
                g, select(cams, vi0))
            tier_caps = auto_tier_caps(occ0, k_tiers, slack=1.25)
        tier_caps = tuple(int(c) for c in tier_caps)
    rfn = _render_batch_jit(grid, K, impl, bg, coarse, k_tiers, tier_caps,
                            assign_impl, assign_budget, coarse_budget)
    rgbs, covs = [], []
    for s in range(0, V, batch):
        take = min(batch, V - s)
        vi = jnp.clip(jnp.arange(s, s + batch), 0, V - 1)
        out = rfn(g, select(cams, vi))
        if k_tiers is not None:
            ov = int(np.asarray(out.overflow).sum())
            while ov and auto_caps:
                # this chunk outgrew the first-chunk caps: double and retry
                # (terminates: caps are clamped at the tile count, where
                # binning provably cannot overflow)
                if schedule is not None:
                    if not schedule.note_overflow(ov, grid.n_tiles):
                        break    # caps already at the clamp: warn below
                    tier_caps = schedule.tier_caps
                else:
                    tier_caps = tuple(min(grid.n_tiles, max(8, 2 * c))
                                      for c in tier_caps)
                rfn = _render_batch_jit(grid, K, impl, bg, coarse, k_tiers,
                                        tier_caps, assign_impl, assign_budget,
                                        coarse_budget)
                out = rfn(g, select(cams, vi))
                ov = int(np.asarray(out.overflow).sum())
            if ov:
                warnings.warn(
                    f"render_views: {ov} tile(s) in views [{s}, {s + take})"
                    f" overflowed the explicit tier_caps={tier_caps} and "
                    "rendered as background; grow the caps (or pass "
                    "tier_caps=None to auto-size)", RuntimeWarning)
        rgbs.append(np.asarray(out.rgb[:take]))
        covs.append(np.asarray(out.coverage[:take]))
    return np.concatenate(rgbs), np.concatenate(covs)


@dataclasses.dataclass
class TimestepData:
    """Everything the distributed driver consumes for one timestep."""
    t: float
    points: np.ndarray
    colors: np.ndarray
    extent: float
    parts: List[PartitionData]
    g0: Gaussians                   # fresh batched (P, N) init: cold-start
    #                                 state AND the restore/warm template
    gts: np.ndarray                 # (P, V, H, W, 3) bg=0 training targets
    masks: Optional[np.ndarray]     # (P, V, H, W) bool, or None


def prepare_timestep(ds: GSDataset, cams: Camera, grid: TileGrid, *,
                     t: float = 0.0, seed: int = 0, n_parts: int = 2,
                     capacity: int, K: int = 48, impl: str = "auto",
                     use_ghost: bool = True,
                     use_mask: bool = True) -> TimestepData:
    """Host-side ingest for ONE timestep of the timeseries driver:
    extraction -> partition (+ghosts) -> fresh equal-capacity (P, N) init
    -> per-partition bg=0 GT renders -> coverage masks.

    This is exactly ``launch/train.py --gs``'s per-scene prep, factored out
    so the streaming loop can run timestep t+1's ingest on a background
    thread (``TimestepPrefetcher``) while timestep t trains on the devices.
    The camera rig and tile grid are FIXED across the series (passed in,
    built once from the t=0 scene), so every timestep's GT tensors share
    one shape; ``capacity`` is likewise series-constant — the warm-started
    state must keep its (P, N) layout — and a partition that outgrows it
    fails loudly rather than silently dropping points.
    """
    points, colors, extent = build_scene(ds, seed, t=t)
    ghost_w = ds.ghost_frac * extent if use_ghost else 0.0
    parts, _ = partition_points(points, colors, n_parts,
                                ghost_width=ghost_w)
    over = [(pd.part_id, len(pd.points)) for pd in parts
            if len(pd.points) > capacity]
    if over:
        raise ValueError(
            f"timestep t={t}: partition(s) {over} exceed the series "
            f"capacity {capacity} — raise the dataset capacity_factor (the "
            "(P, N) layout is fixed across the series by the warm-started "
            "state)")
    g0 = jax.tree.map(lambda *xs: jnp.stack(xs),
                      *[init_partition_gaussians(pd, capacity=capacity)
                        for pd in parts])
    gts, masks = [], []
    for pd in parts:
        part_gt, part_cov = render_views(
            gt_gaussians(pd.points, pd.colors), cams, grid, K=K, impl=impl,
            bg=0.0)
        gts.append(part_gt)
        if use_mask:
            masks.append(coverage_masks(part_cov))
    return TimestepData(
        t=t, points=points, colors=colors, extent=extent, parts=parts,
        g0=g0, gts=np.stack(gts),
        masks=np.stack(masks) if use_mask else None)


class TimestepPrefetcher:
    """One-slot background ingest: ``submit`` schedules a
    ``prepare_timestep`` call on a single worker thread, ``get`` blocks for
    (and clears) the result.  While timestep t trains on the devices, the
    worker extracts/partitions/renders t+1 on the host — jax dispatch is
    thread-safe, so the GT renders interleave with training dispatches and
    the ingest latency hides behind the training wall-clock.  One slot is
    deliberate: prefetching more than one timestep ahead would hold extra
    (P, V, H, W, 3) GT tensors alive for no latency win."""

    def __init__(self):
        self._pool = concurrent.futures.ThreadPoolExecutor(max_workers=1)
        self._fut = None

    def submit(self, fn, /, *args, **kwargs):
        if self._fut is not None:
            raise RuntimeError("prefetch slot already occupied — get() the "
                               "pending timestep first")
        self._fut = self._pool.submit(fn, *args, **kwargs)

    def get(self):
        if self._fut is None:
            raise RuntimeError("nothing prefetched — submit() first")
        fut, self._fut = self._fut, None
        return fut.result()

    def close(self):
        self._pool.shutdown(wait=True)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()
        return False


def run_pipeline(cfg: PipelineCfg) -> PipelineResult:
    ds = get_gs_dataset(cfg.dataset, cfg.tier)
    n_views = cfg.n_views or ds.n_views
    points, colors, extent = build_scene(ds, cfg.seed)
    center = 0.5 * (points.max(0) + points.min(0))
    radius = 1.6 * extent / 2 + 1e-3
    W = H = cfg.resolution
    grid = TileGrid(W, H, cfg.train.tile_h, cfg.train.tile_w)
    cams = orbital_rig(n_views, center, radius, width=W, height=H)

    # global ground truth (full point cloud)
    g_gt = gt_gaussians(points, colors)
    gt_imgs, _ = render_views(g_gt, cams, grid, K=cfg.K)

    # partition (+ optional ghosts)
    ghost_w = ds.ghost_frac * extent if cfg.use_ghost else 0.0
    parts, _ = partition_points(points, colors, cfg.n_parts,
                                ghost_width=ghost_w)

    trained: List[Gaussians] = []
    times: List[float] = []
    key = jax.random.PRNGKey(cfg.seed)
    for pd in parts:
        cap = int(len(pd.points) * ds.capacity_factor) if cfg.densify_every \
            else len(pd.points)
        g0 = init_partition_gaussians(pd, capacity=cap)

        # per-partition GT renders of OWN data (+ghosts) and coverage masks
        part_gt, part_cov = render_views(
            gt_gaussians(pd.points, pd.colors), cams, grid, K=cfg.K)
        masks = coverage_masks(part_cov) if cfg.use_mask else None

        key, sub = jax.random.split(key)
        t0 = time.perf_counter()
        g1, _, _ = fit_partition(
            g0, cams, jnp.asarray(part_gt),
            None if masks is None else jnp.asarray(masks),
            cfg.train, steps=cfg.steps, extent=extent, key=sub,
            densify_every=cfg.densify_every, grid=grid,
        )
        times.append(time.perf_counter() - t0)
        trained.append(g1)

    merged = merge_mod.merge_partitions(trained,
                                        [p.part_id for p in parts])
    renders, _ = render_views(merged, cams, grid, K=cfg.K)

    ps = float(np.mean([
        metrics.psnr(jnp.asarray(renders[v]), jnp.asarray(gt_imgs[v]))
        for v in range(n_views)
    ]))
    ss = float(np.mean([
        metrics.ssim(jnp.asarray(renders[v]), jnp.asarray(gt_imgs[v]))
        for v in range(n_views)
    ]))
    gs = float(np.mean([
        metrics.grad_sim(jnp.asarray(renders[v]), jnp.asarray(gt_imgs[v]))
        for v in range(n_views)
    ]))

    # ---- boundary-region metrics (paper Fig. 2): evaluate on pixels covered
    # by points within the ghost halo of any partition boundary, computed
    # with a FIXED eval halo regardless of cfg.use_ghost so all ablation
    # variants share the same mask
    eval_gw = ds.ghost_frac * extent
    eparts, _ = partition_points(points, colors, cfg.n_parts,
                                 ghost_width=eval_gw)
    bpts = [p.points[p.n_owned:] for p in eparts if p.n_ghost]
    b_ps, b_ss, b_frac = float("nan"), float("nan"), 0.0
    if bpts:
        bpts = np.concatenate(bpts)
        _, bcov = render_views(
            gt_gaussians(bpts, np.zeros_like(bpts)), cams, grid, K=cfg.K)
        # tight mask: substantial boundary coverage only (no dilation —
        # CPU-tier splats are already several pixels wide)
        bmasks = np.stack([np.asarray(c) > 0.5 for c in bcov])
        b_frac = float(bmasks.mean())
        if bmasks.any():
            b_ps = float(np.mean([
                metrics.psnr(jnp.asarray(renders[v]), jnp.asarray(gt_imgs[v]),
                             jnp.asarray(bmasks[v]))
                for v in range(n_views)]))
            b_ss = float(np.mean([
                metrics.ssim(jnp.asarray(renders[v]), jnp.asarray(gt_imgs[v]),
                             jnp.asarray(bmasks[v]))
                for v in range(n_views)]))

    return PipelineResult(
        merged=merged, parts=trained, psnr=ps, ssim=ss, grad_sim=gs,
        train_seconds=times, n_gaussians=int(merged.active.sum()),
        gt_images=gt_imgs, renders=renders,
        boundary_psnr=b_ps, boundary_ssim=b_ss, boundary_frac=b_frac,
    )

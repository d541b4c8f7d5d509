"""Per-partition 3D-GS trainer: per-group Adam + densify/clone/split/prune.

Faithful to Kerbl et al. training dynamics, jit-stable on TPU (DESIGN.md §3):
the gaussian buffer has *fixed capacity* with an ``active`` mask; densify
writes children into free slots (budgeted, ``max_new`` per event) and prune
clears the mask — no reallocation inside jit.  Densification pressure is the
accumulated positional gradient norm, as in the reference.

Every partition of the paper's pipeline runs one instance of this trainer on
its own (owned + ghost) gaussians with its own masked loss; partitions never
exchange gradients (paper §II step 5).
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import NamedTuple, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.cameras import Camera, select
from repro.core.gaussians import Gaussians
from repro.core.masking import gs_loss
from repro.core.render import (occupancy_probe_jit, render_batch,
                               resolve_assignment)
from repro.core.tiling import (DEFAULT_TILE_BUDGET, TierSchedule, TileGrid,
                               grow_tile_budget)
from repro.core.trace import scope


@dataclasses.dataclass(frozen=True)
class GSTrainCfg:
    """Trainer config.  Mesh-axis / tier-schedule contract:

    The trainer rasterizes with OCCUPANCY TIERS by default: ``k_tiers``
    resolves to a K ladder (``"auto"`` derives one from ``K``; an explicit
    tuple pins it; ``None`` — or setting ``dense_k=`` — escapes back to the
    dense fixed-K rasterizer, exactly the pre-tiered behaviour).  ``K`` /
    ``dense_k`` is the dense path's per-tile list depth; in tiered mode the
    assignment depth is the ladder's Kmax and K is ignored.  Tier CAPS are
    not config: they are telemetry, owned by a ``core.tiling.TierSchedule``
    that ``fit_partition`` (and the distributed driver) re-probes after
    every densify/prune; ``tier_slack`` is that schedule's cap headroom.

    On the distributed ("part", "view") mesh (core/distributed.py):
    gaussians + optimizer state are sharded over "part" and replicated over
    "view"; the ``view_batch`` view minibatch is sharded over "view"
    (``view_batch`` must divide by the axis size); ``gather_mode`` /
    ``strip_budget`` shape the "part"-axis table gather and the
    "model"-axis strip work respectively.
    """
    # per-group LRs (3D-GS reference); lr_means is additionally scaled by the
    # scene extent, as in the reference implementation
    lr_means: float = 1.6e-4
    lr_scales: float = 5e-3
    lr_quats: float = 1e-3
    lr_opacity: float = 5e-2
    lr_colors: float = 2.5e-3
    b1: float = 0.9
    b2: float = 0.999
    eps: float = 1e-15
    lambda_dssim: float = 0.2
    K: int = 64
    tile_h: int = 8
    tile_w: int = 16            # CPU default; production (TPU) uses 8x128
    bg: float = 1.0             # white background (paper renders)
    impl: str = "auto"
    view_batch: int = 1         # views per minibatch step (loss = view mean)
    coarse: Optional[int] = None  # superblock pre-cull factor (tiling.py)
    # tile-assignment algorithm: "auto" (sort-based scatter, O(N*B log), on
    # grids of >= tiling.SORTED_MIN_TILES tiles; the O(T*N) dense sweep
    # below — the measured CPU crossover) | "sorted" | "dense" (escape
    # hatch / test oracle); assign_budget is the sorted path's static
    # per-splat tile budget (None = auto, core.tiling.resolve_tile_budget)
    assign_impl: str = "auto"
    assign_budget: Optional[int] = None
    # rasterization schedule: occupancy-tiered by DEFAULT
    #   "auto"  ladder derived from K (e.g. K=64 -> (8, 32, 64))
    #   tuple   explicit ladder, e.g. (16, 64, 256)
    #   None    dense rasterization at K
    k_tiers: Union[str, Tuple[int, ...], None] = "auto"
    dense_k: Optional[int] = None   # escape hatch: dense-K at this depth
    #                                 (disables tiering entirely)
    tier_slack: float = 1.25        # TierSchedule cap headroom over probes
    # densification
    densify_grad_thresh: float = 5e-6
    percent_dense: float = 0.01     # split/clone size boundary (x extent)
    max_new: int = 512              # per densify event (static budget)
    # hard ceiling on LIVE splats per partition (GeoGaussian-style
    # ``num_max``): densify stops adding children once the live count
    # reaches the cap, so memory stays bounded over long / timeseries
    # runs.  None = uncapped (the pre-timeseries behaviour).  Prune still
    # runs below the cap; the cap only gates GROWTH.
    densify_cap: Optional[int] = None
    prune_opacity: float = 0.005
    prune_scale: float = 0.5        # x extent: prune absurdly large splats
    split_shrink: float = 1.6
    # distributed-step options (core/distributed.py; §Perf GS hillclimb)
    gather_mode: str = "f32"        # "f32" (paper baseline) | "split" (bf16)
    strip_budget: float = 1.0       # <1: per-strip candidate prefilter
    # sparse-overlap splat exchange (core/distributed.py): replace the
    # "part"-axis full-table all-gather with a lax.all_to_all under a
    # static per-(src, dst)-edge budget — each device sends only the splats
    # whose tile bboxes overlap the destination's sub-strip.
    # ``exchange_budget=None`` lets fit_partitions probe the budget
    # (distributed.probe_gs_exchange, with ExchangeSchedule slack) and grow
    # it on overflow; an explicit int pins it.
    exchange: bool = False
    exchange_budget: Optional[int] = None
    # mixed precision (core/dtypes.py): "f32" (default; bit-identical to
    # pre-policy builds) | "bf16" — feature tables / collective payloads
    # store bf16, every accumulator (kernel planes, loss, Adam state)
    # stays f32.  Parity per policy is pinned by the per-dtype tolerance
    # ladder in tests/ (docs/mixed-precision.md).
    dtype_policy: str = "f32"
    # gradient compression for the DISTRIBUTED step (optim/compress.py):
    # "none" | "bf16" (stateless round-trip, 2x wire) | "int8" (per-tensor
    # scale + error feedback, 4x wire).  With a mode != "none" the
    # make_gs_train_step signature gains an error-feedback tree that
    # fit_partitions carries in step state and through checkpoints.
    grad_compress: str = "none"

    def __post_init__(self):
        from repro.core.dtypes import check_policy
        check_policy(self.dtype_policy)
        if self.grad_compress not in ("none", "bf16", "int8"):
            raise ValueError(
                f"unknown grad_compress {self.grad_compress!r}; expected "
                "'none', 'bf16' or 'int8'")

    def resolved_k_tiers(self) -> Optional[Tuple[int, ...]]:
        """The active K ladder, or None for dense rasterization.

        ``dense_k`` (the escape hatch) wins over everything; ``"auto"``
        builds a K-capped ladder so the tiered default never assigns deeper
        (= never costs more in the worst case) than the dense K it
        replaces."""
        if self.dense_k is not None or self.k_tiers is None:
            return None
        if self.k_tiers == "auto":
            ladder = []
            for k in (self.K // 8, self.K // 2, self.K):
                k = int(k)
                if k >= 1 and (not ladder or k > ladder[-1]):
                    ladder.append(k)
            return tuple(ladder)
        return tuple(int(k) for k in self.k_tiers)

    @property
    def assign_K(self) -> int:
        """Dense-path assignment depth (``dense_k`` overrides ``K``)."""
        return self.dense_k if self.dense_k is not None else self.K

    def tier_schedule(self) -> Optional[TierSchedule]:
        """A fresh TierSchedule for this cfg, or None when training dense."""
        kt = self.resolved_k_tiers()
        return None if kt is None else TierSchedule(kt, slack=self.tier_slack)


class GSOptState(NamedTuple):
    m: dict
    v: dict
    step: jax.Array
    grad_accum: jax.Array    # (N,) accumulated positional grad norms
    grad_count: jax.Array    # (N,)


def init_opt(g: Gaussians) -> GSOptState:
    """Fresh optimizer state; layout-polymorphic — the densify-stat
    accumulators take the gaussian-index shape, so the single-partition
    (N, ...) layout gets (N,) and the distributed batched (P, N, ...)
    layout gets (P, N)."""
    tr = g.trainable()
    zeros = lambda: jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32), tr)
    acc = g.means.shape[:-1]
    return GSOptState(zeros(), zeros(), jnp.zeros((), jnp.int32),
                      jnp.zeros(acc, jnp.float32), jnp.zeros(acc, jnp.float32))


def group_lrs(cfg: GSTrainCfg, extent: float) -> dict:
    return {
        "means": cfg.lr_means * extent,
        "log_scales": cfg.lr_scales,
        "quats": cfg.lr_quats,
        "opacity_logit": cfg.lr_opacity,
        "colors": cfg.lr_colors,
    }


def _as_view_batch(cam: Camera, gt, mask):
    """Canonicalize (cam, gt, mask) to carry a leading view axis V.

    Accepts either a single view (cam.view (4,4), gt (H,W,3)) or a view
    minibatch (cam.view (V,4,4), gt (V,H,W,3)); the single-view form becomes
    a V=1 batch.  Trace-time branch: jit re-traces per input rank anyway.
    """
    if cam.view.ndim == 2:
        cam = Camera(cam.view[None], jnp.reshape(cam.fx, (1,)),
                     jnp.reshape(cam.fy, (1,)), cam.width, cam.height)
        gt = gt[None]
        mask = None if mask is None else mask[None]
    return cam, gt, mask


#: sentinel: "no explicit k_tiers argument — resolve from the train cfg"
_FROM_CFG = object()


def _check_resume_policy(extra: dict, cfg: GSTrainCfg):
    """Refuse to resume across a dtype-policy / grad-compress boundary.

    A checkpoint trains forward under the SAME numerics it was written
    with: silently switching dtype_policy mid-run would fork the loss
    curve with no record, and switching grad_compress changes the step
    state layout (the int8 error-feedback tree).  Checkpoints that predate
    the knobs carry no record and are treated as the defaults
    ("f32"/"none").  Both drivers (fit_partition / fit_partitions) call
    this on every restore — the CLI surfaces it as a loud, documented
    error rather than a silent divergence."""
    saved_pol = extra.get("dtype_policy", "f32")
    if saved_pol != cfg.dtype_policy:
        raise ValueError(
            f"checkpoint was written under dtype_policy={saved_pol!r} but "
            f"this run uses {cfg.dtype_policy!r}; resume must keep the "
            f"policy — rerun with --dtype-policy {saved_pol} or point "
            "--ckpt-dir at a fresh directory")
    saved_gc = extra.get("grad_compress", "none")
    if saved_gc != cfg.grad_compress:
        raise ValueError(
            f"checkpoint was written under grad_compress={saved_gc!r} but "
            f"this run uses {cfg.grad_compress!r}; resume must keep the "
            "mode (the error-feedback state rides the checkpoint) — rerun "
            f"with --grad-compress {saved_gc} or use a fresh --ckpt-dir")


def make_train_step(cfg: GSTrainCfg, grid: TileGrid, extent: float, *,
                    k_tiers=_FROM_CFG, tier_caps: Optional[tuple] = None,
                    return_overflow: bool = False,
                    assign_impl=_FROM_CFG, assign_budget=_FROM_CFG):
    """Minibatch-of-views train step: cam/gt/mask may carry a leading view
    axis (loss is averaged over the batch); plain single-view inputs still
    work (treated as V=1).

    Rasterization defaults to OCCUPANCY TIERS (``k_tiers`` unset pulls
    ``cfg.resolved_k_tiers()``; ``cfg.dense_k=`` escapes to dense-K).  An
    explicit ``k_tiers=None`` forces dense; a tuple pins the ladder.
    ``tier_caps`` must be static under jit — None falls back to the
    always-exact (but unmeasured) full-grid caps; ``fit_partition`` passes
    measured caps from its ``TierSchedule`` instead.  With
    ``return_overflow=True`` the step returns ``(g, opt, loss, overflow)``
    where overflow is a dict of () int32 counters summed over the view
    batch: ``"tiles"`` — the tiered dropped-tile counter (always 0 on the
    dense path) that ``TierSchedule.note_overflow`` consumes — and
    ``"assign"`` — the tile-ASSIGNMENT budget counter (sorted-path bbox
    slots dropped past ``assign_budget``; always 0 on the dense sweep)
    that the driver feeds to ``tiling.grow_tile_budget`` so radii drifting
    past the probe slack between densify events grow the budget instead of
    truncating silently.  ``assign_impl`` /
    ``assign_budget`` override the cfg's tile-assignment knobs —
    ``fit_partition`` passes host-probed values (a static budget sized
    from concrete bbox counts, or a demotion of "auto" to dense for
    big-splat scenes)."""
    lrs = group_lrs(cfg, extent)
    if k_tiers is _FROM_CFG:
        k_tiers = cfg.resolved_k_tiers()
    if assign_impl is _FROM_CFG:
        assign_impl = cfg.assign_impl
    if assign_budget is _FROM_CFG:
        assign_budget = cfg.assign_budget
    if k_tiers is not None:
        k_tiers = tuple(int(k) for k in k_tiers)
        if tier_caps is None:
            # always-exact fallback: every tier can hold the whole grid
            tier_caps = (grid.n_tiles,) * len(k_tiers)
        tier_caps = tuple(int(c) for c in tier_caps)

    def loss_fn(tr, g: Gaussians, cam: Camera, gt, mask):
        gg = g.with_trainable(tr)
        cam, gt, mask = _as_view_batch(cam, gt, mask)
        out = render_batch(gg, cam, grid, K=cfg.assign_K, impl=cfg.impl,
                           bg=cfg.bg, coarse=cfg.coarse,
                           k_tiers=k_tiers, tier_caps=tier_caps,
                           assign_impl=assign_impl,
                           assign_budget=assign_budget,
                           dtype_policy=cfg.dtype_policy)
        per_view = partial(gs_loss, lambda_dssim=cfg.lambda_dssim)
        if mask is None:
            losses = jax.vmap(lambda p, t: per_view(p, t, None))(out.rgb, gt)
        else:
            losses = jax.vmap(per_view)(out.rgb, gt, mask)
        overflow = {
            "tiles": (jnp.zeros((), jnp.int32) if out.overflow is None
                      else out.overflow.sum().astype(jnp.int32)),
            "assign": (jnp.zeros((), jnp.int32)
                       if out.assign_overflow is None
                       else out.assign_overflow.sum().astype(jnp.int32)),
        }
        return losses.mean(), overflow

    def step(g: Gaussians, opt: GSOptState, cam: Camera, gt, mask=None):
        (loss, overflow), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            g.trainable(), g, cam, gt, mask)
        step_i = opt.step + 1
        bc1 = 1.0 - cfg.b1 ** step_i.astype(jnp.float32)
        bc2 = 1.0 - cfg.b2 ** step_i.astype(jnp.float32)

        def upd(name, p, gr, m, v):
            gr = gr.astype(jnp.float32)
            m = cfg.b1 * m + (1 - cfg.b1) * gr
            v = cfg.b2 * v + (1 - cfg.b2) * gr * gr
            d = (m / bc1) / (jnp.sqrt(v / bc2) + cfg.eps)
            return (p - lrs[name] * d).astype(p.dtype), m, v

        tr = g.trainable()
        new_tr, new_m, new_v = {}, {}, {}
        for k in tr:
            new_tr[k], new_m[k], new_v[k] = upd(k, tr[k], grads[k],
                                                opt.m[k], opt.v[k])
        gnorm = jnp.linalg.norm(grads["means"].astype(jnp.float32), axis=-1)
        new_opt = GSOptState(
            m=new_m, v=new_v, step=step_i,
            grad_accum=opt.grad_accum + gnorm,
            grad_count=opt.grad_count + (gnorm > 0),
        )
        out = (g.with_trainable(new_tr), new_opt, loss)
        return out + (overflow,) if return_overflow else out

    return step


# ---------------------------------------------------------------------------
# Densification (fixed-capacity, budgeted)
# ---------------------------------------------------------------------------


@scope("densify")
def densify_and_prune(g: Gaussians, opt: GSOptState, key, cfg: GSTrainCfg,
                      extent: float):
    """One densify event. Static shapes throughout: up to ``cfg.max_new``
    sources act; children land in free slots found via fixed-size nonzero.
    ``cfg.densify_cap`` additionally bounds the LIVE count: only enough
    children to reach the cap are admitted (the valid (src, free) pairs
    form a prefix of the fixed-size nonzero output, so the cap is a prefix
    mask — static shapes preserved)."""
    cap = g.capacity
    M = min(cfg.max_new, cap)
    avg = opt.grad_accum / jnp.maximum(opt.grad_count, 1.0)
    scales = jnp.exp(g.log_scales)
    smax = scales.max(axis=-1)

    hot = (avg > cfg.densify_grad_thresh) & g.active
    is_split = hot & (smax > cfg.percent_dense * extent)

    src_idx = jnp.nonzero(hot, size=M, fill_value=-1)[0]
    free_idx = jnp.nonzero(~g.active, size=M, fill_value=-1)[0]
    ok = (src_idx >= 0) & (free_idx >= 0)
    if cfg.densify_cap is not None:
        headroom = jnp.maximum(
            jnp.int32(cfg.densify_cap) - g.active.sum().astype(jnp.int32), 0)
        ok = ok & (jnp.arange(M) < headroom)
    # OOB dest indices are dropped by .at[...] mode="drop"
    dest = jnp.where(ok, free_idx, cap)
    src = jnp.where(ok, src_idx, 0)

    src_split = is_split[src]
    # split offset: sample along the gaussian's own shape (R @ (s * eps))
    eps = jax.random.normal(key, (M, 3))
    from repro.core.gaussians import quat_to_rotmat
    R = quat_to_rotmat(g.quats[src])
    offset = (R * (jnp.exp(g.log_scales[src]) * eps)[:, None, :]).sum(-1)
    offset = jnp.where(src_split[:, None], offset, 0.0)
    shrink = jnp.where(src_split[:, None],
                       jnp.log(cfg.split_shrink), 0.0)

    child_means = g.means[src] + offset
    child_ls = g.log_scales[src] - shrink

    at = lambda arr, idx, val: arr.at[idx].set(val, mode="drop")
    new = g._replace(
        means=at(g.means, dest, child_means),
        log_scales=at(g.log_scales, dest, child_ls),
        quats=at(g.quats, dest, g.quats[src]),
        opacity_logit=at(g.opacity_logit, dest, g.opacity_logit[src]),
        colors=at(g.colors, dest, g.colors[src]),
        active=at(g.active, dest, ok),
        owner=at(g.owner, dest, g.owner[src]),
    )
    # split sources shrink in place (the "two children" of the reference:
    # one stays in the source slot, one lands in the free slot)
    upd_src = jnp.where(ok & src_split, src, cap)
    new = new._replace(
        means=new.means.at[upd_src].add(-offset, mode="drop"),
        log_scales=new.log_scales.at[upd_src].add(-jnp.log(cfg.split_shrink),
                                                  mode="drop"),
    )

    # prune: transparent or absurdly large
    alpha = jax.nn.sigmoid(new.opacity_logit)
    keep = (alpha > cfg.prune_opacity) & (jnp.exp(new.log_scales).max(-1)
                                          < cfg.prune_scale * extent)
    new = new._replace(active=new.active & keep)

    # zero adam moments of written slots; reset densify stats
    def zero_at(tree):
        return jax.tree.map(lambda x: x.at[dest].set(0.0, mode="drop"), tree)

    opt = GSOptState(
        m=zero_at(opt.m), v=zero_at(opt.v), step=opt.step,
        grad_accum=jnp.zeros_like(opt.grad_accum),
        grad_count=jnp.zeros_like(opt.grad_count),
    )
    return new, opt


def reset_opacity(g: Gaussians, ceiling: float = 0.01) -> Gaussians:
    """Periodic opacity clamp (reference: counters floaters)."""
    cap_logit = jnp.log(ceiling / (1 - ceiling))
    return g._replace(opacity_logit=jnp.minimum(g.opacity_logit, cap_logit))


# ---------------------------------------------------------------------------
# Convenience host-loop trainer (examples / benchmarks / tests)
# ---------------------------------------------------------------------------


def fit_partition(g: Gaussians, cams: Camera, gts, masks, cfg: GSTrainCfg,
                  *, steps: int, extent: float, key=None,
                  densify_every: int = 0, densify_from: int = 100,
                  log_every: int = 0, grid: Optional[TileGrid] = None,
                  view_batch: Optional[int] = None,
                  schedule: Optional[TierSchedule] = None,
                  ckpt=None, ckpt_every: int = 0,
                  partition: Optional[int] = None,
                  densify_cap: Optional[int] = None):
    """Train one partition for ``steps`` steps cycling over its camera set.

    gts: (V, H, W, 3); masks: (V, H, W) bool or None.  Returns
    (g, opt, losses).  Each step consumes a minibatch of ``view_batch``
    consecutive views (default cfg.view_batch; loss is the view mean)
    rendered through one batched dispatch.

    Tier-schedule lifecycle (tiered-by-default; ``cfg.dense_k=`` opts out):
    a ``TierSchedule`` (``schedule=`` or a fresh one from the cfg) is
    PROBED on the first minibatch's occupancy — unless it already carries
    caps (a resumed/pre-probed schedule trains as-is) — the step trains
    with its static (k_tiers, tier_caps), each densify/prune RE-PROBES
    (occupancy shifted), and any step that reports tiered overflow grows
    the caps — so every cap change is a bounded, telemetry-driven recompile
    and dropped tiles never silently persist.

    Checkpoint/resume: with ``ckpt`` (a runtime.CheckpointManager) the
    newest complete checkpoint is restored — (g, opt) plus the
    TierSchedule state stored alongside them, so the resumed run keeps its
    probed caps instead of re-probing from scratch — the densify key
    stream is fast-forwarded, and training continues from that step;
    ``ckpt_every`` saves periodically (under ``partition_<k>/`` when
    ``partition`` is given).  ``losses`` covers only the steps this call
    actually ran.  core.distributed.fit_partitions is the mesh-parallel
    mirror of this loop.
    """
    if grid is None:
        grid = TileGrid(cams.width, cams.height, cfg.tile_h, cfg.tile_w)
    if key is None:
        key = jax.random.PRNGKey(0)
    sched = schedule if schedule is not None else cfg.tier_schedule()
    # densify_cap= overrides the cfg knob (the timeseries driver passes a
    # computed cap); only the densify closure sees the replaced cfg
    dcfg = dataclasses.replace(cfg, densify_cap=densify_cap) \
        if densify_cap is not None else cfg
    densify = jax.jit(partial(densify_and_prune, cfg=dcfg, extent=extent))
    opt = init_opt(g)
    n_views = gts.shape[0]
    vb = max(1, min(view_batch or cfg.view_batch, n_views))

    start = 0
    if ckpt is not None:
        (g, opt), extra, latest = ckpt.restore_latest((g, opt),
                                                      partition=partition)
        if latest is not None:
            _check_resume_policy(extra, cfg)
            if sched is not None and extra.get("schedule"):
                sched.load_state(extra["schedule"])
            start = latest
    # fast-forward the densify key stream consumed before ``start`` so a
    # resumed run splits the same keys as an uninterrupted one
    for i in range(start):
        if densify_every and i >= densify_from \
                and (i + 1) % densify_every == 0:
            key = jax.random.split(key)[0]

    probe_vi = jnp.arange(min(n_views, max(vb, 2))) % n_views

    # tile-assignment resolution (render.resolve_assignment: probe a
    # static sorted budget from the whole rig's concrete bbox counts, or
    # demote "auto" to dense for big-splat scenes) — re-resolved after
    # every densify, since radii are trained parameters
    assign = {"impl": cfg.assign_impl, "budget": cfg.assign_budget}

    def probe_assign(gg):
        impl, budget = resolve_assignment(gg, cams, grid,
                                          assign_impl=cfg.assign_impl,
                                          assign_budget=cfg.assign_budget)
        assign.update(impl=impl, budget=budget)

    def reprobe(gg):
        occ = occupancy_probe_jit(grid, sched.kmax, cfg.coarse,
                                  assign["impl"], assign["budget"])(
            gg, select(cams, probe_vi))
        sched.probe(occ)

    step_cache = {}

    def get_step():
        spec = ((sched.k_tiers, sched.tier_caps) if sched else None,
                assign["impl"], assign["budget"])
        if spec not in step_cache:
            step_cache[spec] = jax.jit(make_train_step(
                cfg, grid, extent,
                k_tiers=sched.k_tiers if sched else None,
                tier_caps=sched.tier_caps if sched else None,
                return_overflow=True,
                assign_impl=assign["impl"], assign_budget=assign["budget"]))
        return step_cache[spec]

    def note_assign_overflow(ov):
        # the sorted path's static budget truncated candidates this step
        # (radii drifted past the probe slack between densify events): grow
        # it geometrically — the next get_step() rebuilds — mirroring
        # TierSchedule.note_overflow.  Never silent truncation.
        if assign["impl"] != "sorted" or int(np.asarray(ov).sum()) <= 0:
            return
        cur = assign["budget"] or DEFAULT_TILE_BUDGET
        assign["budget"] = grow_tile_budget(cur, grid.n_tiles)

    probe_assign(g)
    if sched is not None and sched.tier_caps is None:
        reprobe(g)
    losses = []
    for i in range(start, steps):
        vi = (i * vb + jnp.arange(vb)) % n_views
        cam = select(cams, vi)
        mask = None if masks is None else masks[vi]
        out = get_step()(g, opt, cam, gts[vi], mask)
        g, opt, loss = out[:3]
        losses.append(float(loss))
        if sched is not None:
            # a non-zero counter grows the caps for the NEXT steps (this
            # step dropped a few tiles — rendered as background in the
            # loss — a one-step blip, not a persistent silent truncation)
            sched.note_overflow(out[3]["tiles"], grid.n_tiles)
        note_assign_overflow(out[3]["assign"])
        if densify_every and i >= densify_from and (i + 1) % densify_every == 0:
            key, sub = jax.random.split(key)
            g, opt = densify(g, opt, sub)
            probe_assign(g)     # splat sizes shifted: re-size the budget
            if sched is not None:
                reprobe(g)      # occupancy shifted: re-pick tiers/caps
        if ckpt is not None and ckpt_every and (i + 1) % ckpt_every == 0:
            ckpt.save(i + 1, (g, opt), partition=partition,
                      extra={"schedule":
                             sched.state_dict() if sched else None,
                             "dtype_policy": cfg.dtype_policy,
                             "grad_compress": cfg.grad_compress})
        if log_every and (i + 1) % log_every == 0:
            print(f"  step {i+1:5d}  loss {losses[-1]:.4f} "
                  f"active {int(g.active.sum())}")
    return g, opt, losses

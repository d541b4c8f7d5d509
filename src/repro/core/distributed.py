"""Distributed 3D-GS training step (paper §II + Grendel [6]), shard_map-native.

Mesh mapping (docs/distributed-training.md has the full guide):

  pod    one spatial partition per pod — *independent* training, the paper's
         node-level parallelism.  Every tensor carries a leading partition
         dim P sharded over "pod"; the only cross-pod traffic is the 4-byte
         scalar-loss psum (metrics), verified in the dry-run HLO.  Optional.
  part   gaussian-parallel: the partition's gaussians are sharded over
         "part"; projection is local; the *projected splat table* (small,
         Grendel's key insight) is all-gathered over "part" — raw gaussians
         and optimizer state never move.  "data" is accepted as a legacy
         alias for this axis.  Required.
  model  pixel-parallel: image tiles are sharded over "model"; each device
         builds top-K lists, rasterizes and evaluates the loss only for its
         own tile strip.  Optional (absent -> every device rasterizes the
         full tile grid for its views).
  view   view-parallel: the view minibatch is sharded over "view" — each
         device projects, gathers and rasterizes only its V/n_view views,
         so the per-device table-gather payload and rasterization work stop
         scaling with the global view batch.  The only collective this axis
         adds is a scalar per-step loss pmean (the per-view losses are
         already averaged with equal weight); gaussians/optimizer state are
         replicated along it, and their gradients are summed across the
         axis by the shard_map transpose automatically.  Optional (absent
         == the degenerate n_view=1 case: views replicated, the pre-2-D
         behaviour).

Canonical production meshes: ``("part", "view")`` for the 2-D trainer and
``("pod", "part", "model")`` for the legacy pixel-sharded layout; any subset
containing a "part"/"data" axis works (see ``_axes``).

Sparse-overlap exchange (``exchange=True`` / cfg.exchange): the full-table
all-gather is the scaling wall at paper-scale splat counts — every device
pays O(N_total) wire bytes per step regardless of how little of the image
its splats touch.  The exchange path replaces it: each device's window is
further split over "part" into per-device sub-windows, each source packs
ONLY the local splats whose tile bboxes overlap each destination's
sub-window (``core.tiling.window_overlap_mask`` — the same bbox math as the
sorted assignment) into a static per-(src, dst) edge budget, and the packed
slabs move via one ``lax.all_to_all`` over "part".  Budgets are probed
(``probe_gs_exchange`` / ``ExchangeSchedule``), overflow is counted and
psum'd — never silent truncation — and the ``fit_partitions`` driver grows
starved budgets geometrically, exactly the probe/overflow honesty contract
the tier schedule and sorted assignment already follow.  The received
table is a src-major, order-preserving subsequence of the all-gather
table, so the two-key (score, index) assignment selects identical splats
and the step matches the gather path to float association.

Implemented with ``shard_map`` + explicit ``lax.all_gather`` so the
collective schedule is *by construction* (an earlier pjit-constraint version
let the SPMD partitioner sink the table all-gather into the tile-assignment
scan and replicate the partition axis across pods through the top-k sort —
500x the wire bytes; see EXPERIMENTS.md §Perf).  The backward pass of
``all_gather`` is ``psum_scatter``, which lands per-gaussian grads back on
their "part" shards automatically.
"""

from __future__ import annotations

import dataclasses
import functools
from functools import partial
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax, shard_map
from jax.sharding import AxisType, NamedSharding, PartitionSpec as P

from repro.core.cameras import CAM_VAXES, Camera, select
from repro.core.dtypes import cast_tables
from repro.core.gaussians import Gaussians
from repro.core.metrics import ssim_map
from repro.core.projection import project
from repro.core.render import resolve_assignment
from repro.core.tiling import (DEFAULT_ASSIGN_IMPL, DEFAULT_TILE_BUDGET,
                               FEAT_DIM, TierSchedule, TileGrid,
                               _merge_block_topk,
                               bin_tiles_by_occupancy, grow_tile_budget,
                               resolve_assign_impl, sorted_assign_window,
                               splat_features, tile_bounds, tile_image,
                               tile_occupancy, tile_tiers,
                               window_overlap_mask)
from repro.core.trace import scope, span, step_span
from repro.core.train import (GSTrainCfg, GSOptState, _check_resume_policy,
                              densify_and_prune, group_lrs, init_opt)
from repro.optim.compress import compress_grads
from repro.kernels import rasterize_tiles
from repro.kernels.ops import rasterize_tiles_tiered

NEG = -1e30


class MeshAxes(NamedTuple):
    """Resolved mesh-axis names; None = axis absent from this mesh."""
    pod: Optional[str]
    data: str            # gaussian axis: "part" (canonical) or "data" alias
    model: Optional[str]
    view: Optional[str]


def _axes(mesh) -> MeshAxes:
    """Map a mesh's axis names onto the four roles above.

    The gaussian axis is mandatory and is named "part" (canonical) or
    "data" (legacy alias); "pod", "model" and "view" are optional.  Any
    other axis name is an error — better loud than silently replicated.
    """
    names = mesh.axis_names
    data = "part" if "part" in names else ("data" if "data" in names else None)
    if data is None:
        raise ValueError(
            "mesh must carry a gaussian axis named 'part' (or legacy "
            f"'data'); got axes {names}")
    ax = MeshAxes(pod="pod" if "pod" in names else None, data=data,
                  model="model" if "model" in names else None,
                  view="view" if "view" in names else None)
    known = {a for a in ax if a is not None}
    extra = [n for n in names if n not in known]
    if extra:
        raise ValueError(f"unknown mesh axes {extra}; expected a subset of "
                         "('pod', 'part'|'data', 'model', 'view')")
    return ax


def _tile_axes(ax: MeshAxes):
    """PartitionSpec entry for the flat (P*T,) tile dim: sharded over the
    present subset of (pod, model), replicated when neither exists."""
    present = tuple(a for a in (ax.pod, ax.model) if a)
    return present if present else None


def check_auto_mesh(mesh):
    """Raise a clear ValueError for a mesh with non-Auto axes.

    The trainer is written for Auto axes (shard_map bodies + jit in/out
    shardings).  Under Explicit axes — ``jax.make_mesh``'s default since
    jax 0.8 — the vmapped densify's fixed-size ``jnp.nonzero`` lowers to a
    scatter whose sharding check fails deep inside XLA."""
    types = getattr(mesh, "axis_types", None) or ()
    bad = [n for n, t in zip(mesh.axis_names, types) if t != AxisType.Auto]
    if bad:
        raise ValueError(
            f"mesh axes {bad} are not AxisType.Auto (got {types}); the GS "
            "trainer needs Auto axes — build the mesh with "
            "repro.launch.mesh.make_mesh (jax.make_mesh defaults to "
            "Explicit axes since jax 0.8)")


def gs_shardings(mesh, *, views: Optional[int] = None):
    """(gaussians, opt, batch) NamedSharding trees for the (P, N) layout.

    Mesh-axis contract (see module docstring / docs/distributed-training.md):
    gaussian + optimizer leaves are sharded (pod, part) on their leading
    (P, N) dims and REPLICATED along "model"/"view"; gt/mask tile batches
    are sharded over (pod, model) on the flat (P*T,) tile dim.

    views=V: gt/mask (and cam.view/fx/fy) gain a leading view axis.  On a
    mesh WITH a "view" axis that leading dim is sharded over it — each
    device holds only V/n_view views and the table all-gather stays on
    "part" with a per-device payload of V/n_view tables.  Without a "view"
    axis the leading dim is replicated (the degenerate n_view=1 case): view
    batches ride along with the gaussian shards and the view axis folds
    into the partition axis inside the shard_map body."""
    ax = _axes(mesh)
    pod, data = ax.pod, ax.data
    tile0 = _tile_axes(ax)
    vlead = (ax.view,) if views else ()
    g = Gaussians(
        means=P(pod, data, None),
        log_scales=P(pod, data, None),
        quats=P(pod, data, None),
        opacity_logit=P(pod, data),
        colors=P(pod, data, None),
        active=P(pod, data),
        owner=P(pod, data),
    )
    ns = lambda spec: NamedSharding(mesh, spec)
    g = Gaussians(*[ns(s) for s in g])
    tr = {k: getattr(g, k) for k in
          ("means", "log_scales", "quats", "opacity_logit", "colors")}
    opt = GSOptState(
        m=dict(tr), v=dict(tr),
        step=ns(P()),
        grad_accum=ns(P(pod, data)),
        grad_count=ns(P(pod, data)),
    )
    cam_v = P(*vlead, None, None) if views else P()
    cam_f = P(*vlead) if views else P()
    batch = {
        "gt_tiles": ns(P(*vlead, tile0, None, None, None)),
        "mask_tiles": ns(P(*vlead, tile0, None, None)),
        "cam": Camera(view=ns(cam_v), fx=ns(cam_f), fy=ns(cam_f),
                      width=ns(P()), height=ns(P())),
    }
    return g, opt, batch


def _all_gather_rows(x, axis_name, nax: int):
    """Tiled all-gather of splat table ``x`` along its row axis ``nax``.

    The collective runs on a (rows, rest) view — rows leading, every other
    dim flattened into one lane-dense minor axis — and the result is moved
    back.  Gathering the (..., N, C) table in place lets the TPU compiler
    lay it out rows-major with the narrow C axis minor, which pads C (and
    the columns the table is built from) to 128 lanes: 18 GB of
    temporaries for a four-chip kingsnake step that needs under 1 GB."""
    xt = jnp.moveaxis(x, nax, 0)
    rows = xt.reshape(xt.shape[0], -1)
    got = lax.all_gather(rows, axis_name, axis=0, tiled=True)
    return jnp.moveaxis(got.reshape((-1,) + xt.shape[1:]), 0, nax)


# ---------------------------------------------------------------------------
# Per-shard (local) pipeline — runs inside shard_map
# ---------------------------------------------------------------------------


@scope("assign")
def _assign_tiles_local(mean2d, radius, depth, valid, lo, hi, *, K: int,
                        block: int, impl: str = "dense",
                        grid: Optional[TileGrid] = None, t0=None,
                        tile_budget: Optional[int] = None):
    """Top-K front-most splats for THIS shard's tile strip.

    mean2d (Pl, N, 2), radius/depth/valid (Pl, N); lo/hi (Tl, 2) strip bounds.
    -> idx (Pl, Tl, K) int32, score (Pl, Tl, K), overflow () int32 — the
    sorted path's dropped bbox-candidate count summed over the partition
    axis (always 0 on the dense sweep, which has no budget to starve);
    the distributed forward psums it into the step's ``"assign"`` counter
    so the driver can grow a starved ``tile_budget`` instead of silently
    truncating.

    ``impl="sorted"`` switches to the duplicate-and-sort scatter
    (core.tiling.sorted_assign_window, vmapped over the partition axis):
    ``grid`` is then the FULL image grid and ``t0`` the (traced) flat-tile
    offset of this shard's strip (None = the strip is the whole grid — the
    "model"-axis-free production mesh).  "auto" resolves on the GLOBAL
    grid's tile count, exactly like the single-device dispatcher, so both
    layouts pick the same algorithm.  Both impls share the two-key
    (score desc, splat index asc) order, so they are bit-identical whenever
    the sorted path's ``tile_budget`` covers the scene — the dense sweep
    stays as the escape hatch / oracle.
    """
    Pl, N = mean2d.shape[:2]
    if grid is not None:
        impl = resolve_assign_impl(impl, grid.n_tiles, tile_budget)
    if impl == "sorted":
        Tl = lo.shape[0]

        def one(m, r, d, v):
            return sorted_assign_window(
                m[:, 0], m[:, 1], r, v, d, grid, K=K, t0=t0, n_local=Tl,
                tile_budget=tile_budget)

        idx, score, ov = jax.vmap(one)(mean2d, radius, depth, valid)
        return idx, score, ov.sum().astype(jnp.int32)
    block = min(block, max(N, K))
    nb = (N + block - 1) // block
    Np = nb * block

    def pad(x, fill=0.0):
        return jnp.pad(x, ((0, 0), (0, Np - N)) + ((0, 0),) * (x.ndim - 2),
                       constant_values=fill)

    mb = pad(mean2d).reshape(Pl, nb, block, 2).transpose(1, 0, 2, 3)
    rb = pad(radius).reshape(Pl, nb, block).transpose(1, 0, 2)
    db = pad(depth, 1e30).reshape(Pl, nb, block).transpose(1, 0, 2)
    vb = jnp.pad(valid, ((0, 0), (0, Np - N)), constant_values=False) \
        .reshape(Pl, nb, block).transpose(1, 0, 2)

    def body(carry, xs):
        top_s, top_i = carry                       # (Pl, Tl, K)
        m, r, d, v, b0 = xs
        # plain slices: a None beside an integer index traces as a gather
        mx, my = m[..., 0][:, None, :], m[..., 1][:, None, :]
        cx = jnp.clip(mx, lo[:, :1], hi[:, :1])      # (Pl, Tl, block)
        cy = jnp.clip(my, lo[:, 1:], hi[:, 1:])
        dx = mx - cx
        dy = my - cy
        hit = (dx * dx + dy * dy) <= (r * r)[:, None, :]
        score = jnp.where(hit & v[:, None, :], -d[:, None, :], NEG)
        # two-key merge (score desc, index asc): the same deterministic
        # tie-break as the global assign_tiles, so strip-local and global
        # assignment agree bit-for-bit even when depths tie at the K
        # boundary (ROADMAP tie-break divergence item)
        return _merge_block_topk(top_s, top_i, score, b0, K), None

    Tl = lo.shape[0]
    init = (jnp.full((Pl, Tl, K), NEG, jnp.float32),
            jnp.zeros((Pl, Tl, K), jnp.int32))
    b0s = jnp.arange(nb, dtype=jnp.int32) * block
    (score, idx), _ = lax.scan(body, init, (mb, rb, db, vb, b0s))
    return idx, score, jnp.zeros((), jnp.int32)


@scope("loss")
def _loss_partials(pred, gt, mask, *, win_size: int = 7):
    """Local partial sums for masked L1 + per-tile D-SSIM.

    pred/gt (Tl', C, th, tw); mask (Tl', th, tw).  Returns 4 scalars
    (l1_num, l1_den, ssim_num, ssim_den) to be psum'd across shards.
    """
    a = pred.astype(jnp.float32)
    b = gt.astype(jnp.float32)
    m = mask.astype(jnp.float32)
    mc = m[:, None]
    l1n = (jnp.abs(a - b) * mc).sum()
    l1d = mc.sum() * a.shape[1]
    sm = jax.vmap(
        lambda x, y: ssim_map(x.transpose(1, 2, 0), y.transpose(1, 2, 0),
                              win_size=win_size)
    )(a, b)                                        # (Tl', th, tw, C)
    sn = (sm * m[..., None]).sum()
    sd = m.sum() * sm.shape[-1]
    return l1n, l1d, sn, sd


def make_gs_forward(mesh, grid: TileGrid, *, K: int, impl: str = "auto",
                    lambda_dssim: float = 0.2,
                    assign_block: Optional[int] = None,
                    return_tiles: bool = False, gather_mode: str = "f32",
                    strip_budget: float = 1.0, views: Optional[int] = None,
                    k_tiers: Optional[tuple] = None,
                    tier_caps: Optional[tuple] = None,
                    return_overflow: bool = False, win_size: int = 7,
                    assign_impl: str = DEFAULT_ASSIGN_IMPL,
                    assign_budget: Optional[int] = None,
                    exchange: bool = False,
                    exchange_budget: Optional[int] = None,
                    dtype_policy: str = "f32"):
    """shard_map'd distributed forward: (gaussians, cam, gt, mask) -> loss.

    ``dtype_policy="bf16"`` (core/dtypes.py) casts BOTH local per-splat
    tables to bf16 BEFORE the "part"-axis collective — the
    all-gather/``all_to_all`` payload halves (and so does its transpose:
    the backward psum-scatter reduces bf16) — and keeps the gathered
    tables in bf16 through the per-tile feature gather; the rasterizer
    promotes to f32 at entry and every accumulator (kernel planes, loss
    partials, psums) stays f32.  The geometry the tile ASSIGNMENT consumes
    (mean2d / radius / depth / valid) is promoted back to f32 right after
    the collective — scoring runs in f32 arithmetic on bf16-ROUNDED
    values, deterministic per policy, so exchange==gather parity holds
    bit-for-bit within the bf16 policy (both paths move identically
    rounded rows).  "f32" (default) is bit-identical to pre-policy builds:
    ``cast_tables`` is the identity and the promotes are same-dtype
    no-ops.  Under ``gather_mode="split"`` the policy additionally drops
    the f32 ``geo`` half to bf16 (the split mode's own ``rest`` table is
    bf16 under every policy).

    ``exchange=True`` swaps the table all-gather for the SPARSE-OVERLAP
    EXCHANGE (module docstring): the window is additionally split over the
    gaussian axis into per-device sub-windows of ``ceil(Tl / n_part)``
    tiles (a strip whose tile count does not divide pads the trailing
    sub-windows with degenerate tiles that hit no splat and are masked out
    of the loss — the padded step still matches the gather path's loss
    exactly, because the masked partials never count pad pixels), each
    source packs only its splats whose bboxes overlap each destination's
    sub-window into static per-(src, dst)-edge slots, and the packed slabs
    move over "part".  ``exchange_budget`` is either a scalar — every edge
    gets the same slot count, moved via one uniform ``lax.all_to_all`` —
    or an (n_part, n_part) int matrix ``B[src, dst]`` of per-edge budgets
    (``ExchangeSchedule``/``probe_gs_exchange(per_edge=True)``), realized
    as a RAGGED exchange: ``lax.all_to_all`` requires uniform chunks, so
    the matrix is carried by a ppermute ladder — one shifted permute per
    ring offset k, whose static slab height is the worst edge ON THAT
    SHIFT (``max_src B[src, (src+k) % n]``) — and each source additionally
    masks its slab past its OWN edge budget, so the per-edge cap is exact
    and the per-device wire payload is ``sum_k max_src B[src, (src+k)%n]``
    rows instead of ``n_part * max_edge``.  Received slabs are re-packed
    src-major (traced offsets from the static per-shift sizes), keeping
    the table an order-preserving subsequence of the all-gather table — so
    the two-key (score, index) top-k still selects identical splats and
    exchange==gather parity holds at float association whenever the
    per-edge overflow counters are zero.  ``exchange_budget=None``
    defaults to the local table size (always exact, payload == all_gather
    — pass a probed budget for the sparse win); a starved edge drops its
    overflowing splats from the receiver's table and FIRES the psum'd
    ``"exchange"`` overflow counter (see ``return_overflow``) — the output
    stays well-formed, and the ``fit_partitions`` driver grows the budget.
    Each device rasterizes (and pays loss partials for) only its own
    sub-window, so per-device rasterization work also drops by the
    gaussian-axis size relative to the gather path's redundant strips.
    Incompatible with ``strip_budget < 1.0`` (the prefilter is the gather
    path's halfway optimization; exchange subsumes it — a loud,
    deliberate validation, not a TODO).  With ``return_tiles=True`` the
    tiles come back UNFLATTENED as ([V,] P, T, 4, th, tw) — the flat
    (P*T,) layout of the gather path would interleave sub-windows
    non-contiguously, so return_tiles DOES still require the strip tile
    count to divide by the gaussian-axis size (pad sub-windows cannot
    reassemble into the (P, T) tile layout; the loss-only path has no such
    restriction).

    ``assign_impl`` selects the strip-local tile assignment: "auto" (the
    default — sort-based scatter on grids past the measured tile-count
    crossover, dense sweep below; resolved on the GLOBAL grid so every
    layout of one scene picks the same algorithm), "sorted"
    (duplicate-and-sort scatter, O(N*B log) independent of the strip tile
    count) or "dense" (the O(Tl*N) sweep — escape hatch / test oracle);
    both share the two-key tie-break, so the step's math is IDENTICAL
    whenever the sorted path's static per-splat ``assign_budget`` covers
    the scene (test_distributed.py pins sorted == dense through the 2-D
    mesh step).  ``assign_block`` only shapes the dense sweep's
    temporaries.

    ``win_size`` is the per-tile D-SSIM window (default 7: tiles are as
    small as 8 pixels tall, see masking.tile_l1_dssim_loss; a grid whose
    single tile covers the whole image with win_size=11 reproduces the
    single-device full-image gs_loss exactly — the driver parity tests
    pin this).

    gt_tiles (P*T, 3, th, tw) / mask_tiles (P*T, th, tw) arrive sharded over
    ("pod", "model") on the flat tile axis.

    k_tiers=(16, 64, 256)-style schedules switch each device's strip to
    occupancy-tiered rasterization: the strip-local assignment runs at
    k_tiers[-1] (K is then ignored), the strip's (Pl*Tl,) flat tiles are
    binned with core.tiling.bin_tiles_by_occupancy — the SAME binning as
    the single-device renderer, so tiered distributed == tiered
    single-device — and each non-empty tier gets its own kernel launch,
    scattered back into the strip image.  tier_caps are static per-strip
    tile capacities shared by all devices (they must cover the worst
    strip); None defaults to the always-exact full strip size (no tile is
    ever dropped, but every tier launch is strip-sized — pass measured
    caps in production).  ``return_overflow=True`` appends a DICT of three
    globally psum'd () int32 counters to the outputs — ``"tiles"`` (tiered
    dropped tiles; 0 == the tiered step is exact), ``"assign"`` (sorted
    assignment's dropped bbox candidates past ``assign_budget``) and
    ``"exchange"`` (splats dropped past a starved ``exchange_budget``; 0
    on the gather path) — the telemetry ``fit_partitions`` consumes for
    geometric budget growth, mirroring RenderOut.overflow /
    RenderOut.assign_overflow on the single-device path.  No counter is
    ever silently swallowed: every truncation path in the step reports
    here.

    views=V enables the view-batched step: cam carries (V, 4, 4) view
    matrices, gt/mask gain a leading V axis, and the loss is the MEAN OF
    PER-VIEW losses (each view's masked pixel normalization stays its own —
    the same equal-view weighting as train.py's minibatch step).  On a mesh
    WITHOUT a "view" axis that leading axis is replicated; on a 2-D
    ``("part", "view")``-style mesh it is SHARDED over "view": each device
    projects/gathers/rasterizes only its V/n_view views, the table
    all-gather stays on "part" only (per-device payload V/n_view tables,
    not V), and the collective schedule grows exactly one cheap "view"-axis
    loss pmean rather than a second gather.  Inside the shard body the
    local view axis is folded into the partition axis right after the table
    all-gather, so tile assignment and the kernel launch (one
    (Vl*Pl*Tl,) grid) are shared verbatim with the single-view path; the
    loss psum carries (Vl,) vectors instead of scalars.  V must divide by
    the "view" axis size; the view=1 (or axis-absent) case degenerates to
    the replicated pre-2-D behaviour bit-for-bit.

    Beyond-paper options (EXPERIMENTS.md §Perf, GS hillclimb):

    gather_mode="split"  all-gather two compact tables instead of one f32
        feature table + aux: ``geo`` (mx, my, radius, depth) f32 — pixel
        coordinates need f32 at 2048^2 — and ``rest`` (conic, rgb, alpha)
        bf16.  32 B/splat on the wire vs 76 B baseline (2.4x collective).
    strip_budget<1.0     per-device tile strips cover ~1/n_model of the
        image: prefilter gathered splats to those whose y-span touches MY
        strip and compact to a budget of ceil(N*strip_budget) before the
        O(T_l x N) assignment sweep — the dominant memory/compute term
        scales down by the strip hit rate (~1/n_model + halo).  The budget
        must exceed the true strip occupancy or overflow splats are dropped
        (set >= 3x the mean occupancy; exactness tested at budget 1.0).
    """
    ax = _axes(mesh)
    pod, data, model, view = ax
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_model = sizes.get(model, 1)
    n_view = sizes.get(view, 1)
    if views is None and n_view > 1:
        raise ValueError(
            f"mesh has a 'view' axis of size {n_view} but views=None; pass "
            f"views=V (a multiple of {n_view}) to shard the view minibatch")
    vloc = None
    if views is not None:
        if views % n_view:
            raise ValueError(f"views={views} must divide by the 'view' axis "
                             f"size {n_view}")
        vloc = views // n_view           # per-device view count
    T = grid.n_tiles
    assert T % n_model == 0, (T, n_model)
    Tl = T // n_model
    n_data = sizes[data]
    sub, pad = Tl, 0
    ex_budget_mat = None
    if exchange:
        if strip_budget < 1.0:
            raise ValueError(
                "exchange=True subsumes the strip prefilter; "
                f"strip_budget must stay 1.0 (got {strip_budget})")
        sub = -(-Tl // n_data)                  # ceil: pad, never refuse
        pad = sub * n_data - Tl
        if pad and return_tiles:
            raise ValueError(
                f"return_tiles with exchange=True needs the {Tl}-tile "
                f"window to divide by the '{data}' axis (size {n_data}): "
                "padded sub-windows cannot reassemble into the (P, T) "
                "tile layout (the loss-only path pads instead)")
        if exchange_budget is not None and np.ndim(exchange_budget) != 0:
            ex_budget_mat = check_budget_matrix(exchange_budget, n_data)
    tile0 = _tile_axes(ax)
    if k_tiers is not None:
        k_tiers = tuple(int(k) for k in k_tiers)
        K = k_tiers[-1]                  # assignment depth = largest tier
    if assign_block is None:
        # auto block: the view fold multiplies the assign sweep's leading
        # axis by the LOCAL view count, so shrink the gaussian block to keep
        # per-device peak temporaries roughly view-count independent
        # (mirrors render_batch's auto block).  An explicit assign_block is
        # honored verbatim.
        assign_block = max(1024, 4096 // vloc) if views else 4096

    g_spec = Gaussians(
        means=P(pod, data, None), log_scales=P(pod, data, None),
        quats=P(pod, data, None), opacity_logit=P(pod, data),
        colors=P(pod, data, None), active=P(pod, data), owner=P(pod, data),
    )
    vlead = (view,) if views else ()
    cam_spec = Camera(view=P(*vlead, None, None) if views else P(),
                      fx=P(*vlead) if views else P(),
                      fy=P(*vlead) if views else P(),
                      width=P(), height=P())
    in_specs = (g_spec, cam_spec, P(*vlead, tile0, None, None, None),
                P(*vlead, tile0, None, None))
    if exchange:
        # unflattened ([V,] P, T, ...) tiles: the T axis shards over
        # (model-major, part-minor), exactly the sub-window decomposition
        # t = mi*Tl + pi*sub — each device's chunk is contiguous there,
        # which the flat (P*T,) layout can't offer for P > 1
        win_axes = tuple(a for a in (model, data) if a)
        tiles_spec = P(*vlead, pod, win_axes, None, None, None)
    else:
        tiles_spec = P(*vlead, tile0, None, None, None)
    out_specs = (P(),)
    if return_tiles:
        out_specs += (tiles_spec,)
    if return_overflow:
        ov_spec = {"tiles": P(), "assign": P(), "exchange": P()}
        if ex_budget_mat is not None:
            # per-edge telemetry (replicated (n, n) matrices): psum'd
            # dropped-splat counts and the pmax'd in-step demand probe
            ov_spec["exchange_edges"] = P()
            ov_spec["exchange_demand"] = P()
        out_specs += (ov_spec,)
    out_specs = out_specs if len(out_specs) > 1 else P()

    lo_full, hi_full = tile_bounds(grid)            # (T, 2) host constants
    lo_pad = hi_pad = None
    if exchange and pad:
        # padded per-strip rect tables: each strip's Tl real tiles followed
        # by `pad` degenerate rects (lo > hi) no circle can hit — pad slots
        # assign nothing, rasterize to zeros and are loss-masked below
        lo_np, hi_np = np.asarray(lo_full), np.asarray(hi_full)
        lo_w = np.full((n_model * n_data * sub, 2), 1e9, np.float32)
        hi_w = np.full((n_model * n_data * sub, 2), -1e9, np.float32)
        for mi in range(n_model):
            lo_w[mi * n_data * sub: mi * n_data * sub + Tl] = \
                lo_np[mi * Tl: (mi + 1) * Tl]
            hi_w[mi * n_data * sub: mi * n_data * sub + Tl] = \
                hi_np[mi * Tl: (mi + 1) * Tl]
        lo_pad, hi_pad = jnp.asarray(lo_w), jnp.asarray(hi_w)
    # all-gather axis: N sits one deeper when a view axis leads
    nax = 2 if views else 1

    def shard_fn(g: Gaussians, cam: Camera, gt, mask):
        # ---- stage 1 (gaussian-parallel over "part"): project locally.
        # With a "view" mesh axis, cam/gt/mask arrive already view-sharded:
        # this body only ever sees its Vl = V/n_view local views.
        with scope("project"):
            if views:
                # (Vl, Pl, Nl, ...): per-view projection of the same local shard
                splats = jax.vmap(lambda c: project(g, c),
                                  in_axes=(CAM_VAXES,))(cam)
            else:
                splats = project(g, cam)                # (Pl, Nl, ...)

            # ---- local compact tables: the per-splat rows both handoffs move
            if gather_mode == "split":
                radius_v = jnp.where(splats.valid, splats.radius, 0.0)
                geo_l = jnp.stack(
                    [splats.mean2d[..., 0], splats.mean2d[..., 1],
                     radius_v, splats.depth], axis=-1)             # (Pl,Nl,4) f32
                a, b, c = (splats.cov2d[..., 0], splats.cov2d[..., 1],
                           splats.cov2d[..., 2])
                det = jnp.maximum(a * c - b * b, 1e-12)
                alpha_v = jnp.where(splats.valid, splats.alpha, 0.0)
                rest_l = jnp.stack(
                    [c / det, -b / det, a / det,
                     splats.rgb[..., 0], splats.rgb[..., 1], splats.rgb[..., 2],
                     alpha_v, jnp.zeros_like(alpha_v)],
                    axis=-1).astype(jnp.bfloat16)                  # (Pl,Nl,8)
                tabs_l = (geo_l, rest_l)
            else:
                feat_l = splat_features(splats)                    # (Pl,Nl,F)
                aux_l = jnp.stack(
                    [splats.radius, splats.depth,
                     splats.valid.astype(jnp.float32)], axis=-1)   # (Pl,Nl,3)
                tabs_l = (feat_l, aux_l)

            # mixed-precision boundary: drop the wire tables to the policy's
            # storage dtype BEFORE the collective (identity under "f32") —
            # payload halves here, and the backward psum-scatter of the
            # all-gather reduces in the same dtype (honest 2x both directions)
            tabs_l = cast_tables(tabs_l, dtype_policy)

        fold = lambda x: x.reshape((-1,) + x.shape[2:])
        t0_strip = lax.axis_index(model) * Tl if model is not None else None

        with scope("transport"):
            if exchange:
                # ---- sparse-overlap exchange: pack only the splats whose
                # bboxes overlap each destination's sub-window (module
                # docstring).  A scalar budget moves one uniform all_to_all;
                # a per-edge budget matrix moves a ragged ppermute ladder.
                if views:
                    tabs_l = tuple(fold(x) for x in tabs_l)        # (R, Nl, C)
                Nl = tabs_l[0].shape[1]
                # overlap geometry in f32 (promote is a no-op under "f32"):
                # the send-side bbox test must run the same arithmetic as the
                # receive-side assignment on the same rounded values
                mx_l = tabs_l[0][..., 0].astype(jnp.float32)
                my_l = tabs_l[0][..., 1].astype(jnp.float32)
                if gather_mode == "split":
                    rad_l = tabs_l[0][..., 2].astype(jnp.float32)
                    val_l = rad_l > 0                  # geo radius, valid-masked
                else:
                    rad_l = tabs_l[1][..., 0].astype(jnp.float32)  # aux (raw)
                    val_l = tabs_l[1][..., 2] > 0.5
                base = 0 if t0_strip is None else t0_strip
                t0_all = base + jnp.arange(n_data, dtype=jnp.int32) * sub
                # t_end clips padded sub-windows at the strip's real tiles:
                # pad slots pack (and count) nothing, partial windows never
                # charge the next strip's rows against an edge budget
                hit = window_overlap_mask(mx_l, my_l, rad_l, val_l, grid,
                                          t0=t0_all, n_local=sub,
                                          t_end=(base + Tl) if pad else None)
                # hit (n_data, R, Nl): slab d = MY splats destined for the
                # device at part-index d.  Candidates past the edge budget are
                # counted, never silently dropped.
                counts = hit.sum(-1, dtype=jnp.int32)
                if ex_budget_mat is None:
                    E = min(int(exchange_budget), Nl) if exchange_budget \
                        else Nl
                    exchange_ov_l = jnp.maximum(counts - E, 0).sum() \
                        .astype(jnp.int32)
                    slots = jax.vmap(jax.vmap(
                        lambda m: jnp.nonzero(m, size=E, fill_value=Nl)[0]))(hit)

                    def exch(x):
                        sent = jax.vmap(lambda s: jax.vmap(
                            lambda row, i: jnp.take(row, i, axis=0, mode="fill",
                                                    fill_value=0))(x, s))(slots)
                        got = lax.all_to_all(sent, data, 0, 0, tiled=True)
                        # got's axis 0 is the SOURCE part index: flattening it
                        # src-major keeps ascending local rows inside each
                        # source — an order-preserving subsequence of the
                        # all-gather table, so the two-key (score, index) top-k
                        # selects the identical splats whenever E covers.  Fill
                        # slots carry radius 0 / valid 0: dead to assignment
                        # and compositing.
                        return got.transpose(1, 0, 2, 3).reshape(
                            (got.shape[1], n_data * E) + got.shape[3:])
                else:
                    # ---- ragged per-edge transport: all_to_all needs uniform
                    # chunks, so the (n, n) budget matrix rides a ppermute
                    # LADDER — ring shift k carries every (s -> (s+k) % n) edge
                    # at once in a slab sized by the worst edge on that shift;
                    # each source masks its slab past its own B[src, dst], so
                    # the per-edge cap is exact and the wire payload is
                    # sum_k E_shift[k] rows, not n * max(B).
                    Bm = np.minimum(ex_budget_mat, Nl).astype(np.int32)
                    ring = (np.arange(n_data) + np.arange(n_data)[:, None]) \
                        % n_data                       # ring[k, s] = (s+k) % n
                    # overlap-aware window assignment: device i renders band
                    # tau[i], chosen so each brick's dominant band rides the
                    # free local shift (window_assignment docstring).  The
                    # (P, T) tile layout of return_tiles is band-ordered, so
                    # that path keeps the identity assignment.
                    tau_np = np.arange(n_data, dtype=np.int64) if return_tiles \
                        else window_assignment(Bm)
                    tau_arr = jnp.asarray(tau_np, jnp.int32)
                    band = tau_np[ring]        # band[k, s]: dst band, shift k
                    E_shift = tuple(
                        int(Bm[np.arange(n_data), band[k]].max())
                        for k in range(n_data))
                    R_tot = int(sum(E_shift))
                    me = lax.axis_index(data)
                    b_row = jnp.take(jnp.asarray(Bm), me, axis=0)      # (n,)
                    exchange_ov_edges = jnp.maximum(
                        counts - b_row[:, None], 0).sum(1).astype(jnp.int32)
                    exchange_ov_l = exchange_ov_edges.sum()
                    exchange_demand_l = counts.max(1).astype(jnp.int32)
                    slot_by_shift = []
                    for k in range(n_data):
                        # rows for the BAND the shift-k destination renders
                        hk = jnp.take(hit, jnp.take(tau_arr, (me + k) % n_data),
                                      axis=0)                          # (R, Nl)
                        sl = jax.vmap(
                            lambda m, _E=E_shift[k]: jnp.nonzero(
                                m, size=_E, fill_value=Nl)[0])(hk)
                        # my own edge budget on this shift, B[me, tau[(me+k)
                        # % n]]: slots past it become fill rows (counted above)
                        cap = jnp.take(
                            jnp.asarray(Bm[np.arange(n_data), band[k]]), me)
                        slot_by_shift.append(
                            jnp.where(jnp.arange(E_shift[k]) < cap, sl, Nl))
                    # receive side: shift k delivers src (me - k) % n; packing
                    # the slabs back in SRC order (exclusive cumsum of the
                    # static per-shift sizes, permuted to src order) keeps the
                    # table an order-preserving subsequence of the all-gather
                    # table — same two-key top-k parity as the uniform path
                    src_shift = (me - jnp.arange(n_data)) % n_data
                    sizes_by_src = jnp.take(
                        jnp.asarray(E_shift, jnp.int32), src_shift)
                    offs = jnp.concatenate(
                        [jnp.zeros((1,), jnp.int32),
                         jnp.cumsum(sizes_by_src)[:-1].astype(jnp.int32)])

                    def exch(x):
                        out = jnp.zeros((x.shape[0], R_tot) + x.shape[2:],
                                        x.dtype)
                        for k in range(n_data):
                            sent = jax.vmap(
                                lambda row, i: jnp.take(
                                    row, i, axis=0, mode="fill",
                                    fill_value=0))(x, slot_by_shift[k])
                            got = sent if k == 0 else lax.ppermute(
                                sent, data,
                                perm=[(s, (s + k) % n_data)
                                      for s in range(n_data)])
                            off = jnp.take(offs, (me - k) % n_data)
                            out = lax.dynamic_update_slice_in_dim(
                                out, got, off, axis=1)
                        return out

                tabs = tuple(exch(x) for x in tabs_l)
            else:
                # ---- Grendel handoff: all-gather the SMALL projected table
                # over "part".  bwd(all_gather) = psum_scatter -> grads return
                # sharded.
                tabs = tuple(_all_gather_rows(x, data, nax) for x in tabs_l)
                if views:
                    # fold the LOCAL view axis into the partition axis:
                    # (Vl, Pl, ...) -> (Vl*Pl, ...) — stage 2 and the kernel
                    # launch are view-count agnostic
                    tabs = tuple(fold(x) for x in tabs)
                exchange_ov_l = jnp.zeros((), jnp.int32)

        # assignment geometry promotes to f32 (no-op under "f32"): scoring
        # and depth ordering run f32 arithmetic on the policy-rounded
        # values; the kernel feature tables (feat / rest) STAY in the
        # storage dtype — halved gather volume is the point
        if gather_mode == "split":
            geo, rest = tabs
            geo = geo.astype(jnp.float32)
            mean_g = geo[..., 0:2]
            radius_g = geo[..., 2]
            depth_g = geo[..., 3]
            valid_g = radius_g > 0
        else:
            feat, aux = tabs
            mean_g = feat[..., 0:2].astype(jnp.float32)
            radius_g = aux[..., 0].astype(jnp.float32)
            depth_g = aux[..., 1].astype(jnp.float32)
            valid_g = aux[..., 2] > 0.5

        # ---- stage 2 (pixel-parallel over "model"): my tile window — the
        # model-axis strip, further split over "part" into sub-windows
        # under exchange; without either axis the window is the whole grid
        if exchange:
            pi = lax.axis_index(data)
            if ex_budget_mat is not None:
                # window assignment: this device renders band tau[me] of
                # its strip (loss partials psum across "part", so the loss
                # is assignment-invariant; gt/mask slice the same band)
                pi = jnp.take(tau_arr, pi)
            t0 = (0 if t0_strip is None else t0_strip) + pi * sub
            if pad:
                # slice the PADDED per-strip rect table (strip-major window
                # index), so pad slots get degenerate rects no circle hits
                mi = lax.axis_index(model) if model is not None else 0
                w0 = (mi * n_data + pi) * sub
                lo = lax.dynamic_slice_in_dim(lo_pad, w0, sub, 0)
                hi = lax.dynamic_slice_in_dim(hi_pad, w0, sub, 0)
            else:
                lo = lax.dynamic_slice_in_dim(lo_full, t0, sub, 0)
                hi = lax.dynamic_slice_in_dim(hi_full, t0, sub, 0)
        elif model is not None:
            t0 = t0_strip                    # strip's flat-tile offset
            lo = lax.dynamic_slice_in_dim(lo_full, t0, Tl, 0)
            hi = lax.dynamic_slice_in_dim(hi_full, t0, Tl, 0)
        else:
            t0 = None                        # window == the whole grid
            lo, hi = lo_full, hi_full
        Wl = sub if exchange else Tl

        if exchange:
            # gt/mask arrive replicated along "part" with the full strip's
            # tiles: slice MY sub-window out of each partition's block
            # (zero-padding the strip's tile axis first when it does not
            # divide — pad tiles carry mask=0, so the masked loss partials
            # never count them and the loss equals the gather loss exactly)
            def subwin(x):
                lead = 1 if views else 0
                y = x.reshape(x.shape[:lead] + (-1, Tl) + x.shape[lead + 1:])
                if pad:
                    widths = [(0, 0)] * y.ndim
                    widths[lead + 1] = (0, pad)
                    y = jnp.pad(y, widths)
                y = lax.dynamic_slice_in_dim(y, pi * sub, sub, lead + 1)
                return y.reshape(x.shape[:lead] + (-1,) + x.shape[lead + 1:])
            gt = subwin(gt)
            mask = subwin(mask)

        N = mean_g.shape[1]
        if strip_budget < 1.0:
            # strip prefilter: only splats whose circle touches MY strip
            ylo = lo[:, 1].min()
            yhi = hi[:, 1].max()
            touch = (valid_g
                     & (mean_g[..., 1] + radius_g >= ylo)
                     & (mean_g[..., 1] - radius_g <= yhi))
            M = -(-int(N * strip_budget) // 128) * 128
            cand = jax.vmap(
                lambda m: jnp.nonzero(m, size=M, fill_value=N)[0])(touch)
            take = lambda x: jax.vmap(
                lambda arr, i: jnp.take(arr, i, axis=0, mode="fill",
                                        fill_value=0))(x, cand)
            mean_g, radius_g, depth_g = (take(mean_g), take(radius_g),
                                         take(depth_g))
            valid_g = take(valid_g.astype(jnp.float32)) > 0.5
            if gather_mode == "split":
                rest = take(rest)
            else:
                feat = take(feat)

        idx, score, assign_ov_l = _assign_tiles_local(
            mean_g, radius_g, depth_g, valid_g,
            lo, hi, K=K, block=assign_block, impl=assign_impl,
            grid=grid, t0=t0, tile_budget=assign_budget)
        idx = lax.stop_gradient(idx)
        live = lax.stop_gradient(score) > NEG / 2   # (Pl, Tl, K)

        @scope("gather")
        def features_for(p_rows, idx_rows, live_rows):
            """Kernel features for arbitrary tile rows: p_rows (...,) picks
            the partition slice of the gathered table, idx_rows (..., K')
            the splat rows within it, live_rows masks dead slots' alpha.
            Serves both the dense (Pl, Tl, K) gather and the per-tier
            compacted (cap_i, K_i) gathers."""
            if gather_mode == "split":
                mean_t = mean_g[p_rows[..., None], idx_rows]
                rest_t = rest[p_rows[..., None], idx_rows] \
                    .astype(jnp.float32)
                alpha = jnp.where(live_rows, rest_t[..., 6], 0.0)
                return jnp.concatenate(
                    [mean_t, rest_t[..., :6], alpha[..., None],
                     jnp.zeros(mean_t.shape[:-1] + (FEAT_DIM - 9,),
                               jnp.float32)], axis=-1)
            feat_t = feat[p_rows[..., None], idx_rows]
            alpha = jnp.where(live_rows, feat_t[..., 8], 0.0)
            return jnp.concatenate(
                [feat_t[..., :8], alpha[..., None], feat_t[..., 9:]], -1)

        with scope("raster"):
            Pl = mean_g.shape[0]
            origins = jnp.tile(lo, (Pl, 1))                 # (Pl*Tl, 2)
            if k_tiers is not None:
                # ---- tiered dispatch over the window's flat tile axis ----
                M = Pl * Wl
                idx_f = idx.reshape(M, K)
                live_f = live.reshape(M, K)
                occ = live_f.sum(-1).astype(jnp.int32)
                caps = tier_caps if tier_caps is not None \
                    else (M,) * len(k_tiers)
                plan = bin_tiles_by_occupancy(occ, k_tiers, caps)
                overflow_l = plan.overflow
                tier_feats, tier_origins = [], []
                for k, ids in zip(k_tiers, plan.tile_ids):
                    safe = jnp.minimum(ids, M - 1)          # sentinel-safe rows
                    live_rows = live_f[safe, :k] & (ids < M)[:, None]
                    tier_feats.append(
                        features_for(safe // Wl, idx_f[safe, :k], live_rows))
                    tier_origins.append(jnp.take(origins, ids, axis=0,
                                                 mode="fill", fill_value=0.0))
                tiles = rasterize_tiles_tiered(
                    tier_feats, tier_origins, plan.tile_ids, M,
                    tile_h=grid.tile_h, tile_w=grid.tile_w, impl=impl)
            else:
                p_rows = jnp.broadcast_to(
                    jnp.arange(Pl, dtype=jnp.int32)[:, None], idx.shape[:2])
                tile_feat = features_for(p_rows, idx, live)  # (Pl,Wl,K,F)
                flat = tile_feat.reshape(Pl * Wl, K, FEAT_DIM)
                tiles = rasterize_tiles(flat, origins, tile_h=grid.tile_h,
                                        tile_w=grid.tile_w, impl=impl)
                overflow_l = jnp.zeros((), jnp.int32)   # dense path never drops

        # ---- masked loss partials -> psum (scalar-only cross-pod traffic).
        # The partial psum runs over the present (pod, part, model) axes —
        # it must NOT cross "view" shards, whose partials belong to
        # different views; the view axis contributes one scalar pmean at
        # the very end instead.
        with scope("loss"):
            axes = tuple(a for a in (pod, data, model) if a)
            if views:
                # per-view partials ((Vl,) vectors through the psum), then the
                # mean of per-view losses — the same equal-view weighting as
                # train.py's minibatch step, regardless of how many masked
                # pixels each view has.  mean over local views + pmean over the
                # "view" axis == the global V-view mean (equal local counts).
                pred_v = tiles[:, :3].reshape((vloc, -1, 3) + tiles.shape[2:])
                l1n, l1d, sn, sd = jax.vmap(
                    partial(_loss_partials, win_size=win_size))(pred_v, gt, mask)
                l1n, l1d, sn, sd = (lax.psum(x, axes) for x in (l1n, l1d, sn, sd))
                loss = ((1 - lambda_dssim) * l1n / jnp.maximum(l1d, 1.0)
                        + lambda_dssim
                        * (1.0 - sn / jnp.maximum(sd, 1.0)) / 2.0).mean()
                if view is not None:
                    loss = lax.pmean(loss, view)
            else:
                l1n, l1d, sn, sd = _loss_partials(tiles[:, :3], gt, mask,
                                                  win_size=win_size)
                l1n, l1d, sn, sd = (lax.psum(x, axes) for x in (l1n, l1d, sn, sd))
                loss = ((1 - lambda_dssim) * l1n / jnp.maximum(l1d, 1.0)
                        + lambda_dssim * (1.0 - sn / jnp.maximum(sd, 1.0)) / 2.0)
        if return_tiles or return_overflow:
            outs = (loss,)
            if return_tiles:
                if exchange:
                    # unflattened ([Vl,] Pl, Wl, ...) — see tiles_spec
                    lead = (vloc, -1, Wl) if views else (-1, Wl)
                    tiles = tiles.reshape(lead + tiles.shape[1:])
                elif views:
                    tiles = tiles.reshape((vloc, -1) + tiles.shape[1:])
                outs += (tiles,)
            if return_overflow:
                # tiles/assign counters: each window is computed once per
                # strip-distinct device; under gather the "part" devices
                # hold REDUNDANT copies of the strip (summing across them
                # would multiply by n_part), under exchange they hold
                # DISTINCT sub-windows (the sum must cross "part" too).
                # The exchange counter is send-side and per-device-distinct
                # always: sum over every axis.
                strip_axes = tuple(a for a in (pod, model, view) if a) \
                    + ((data,) if exchange else ())
                red = (lambda x: lax.psum(x, strip_axes)) if strip_axes \
                    else (lambda x: x)
                all_axes = tuple(a for a in (pod, data, model, view) if a)
                ov_out = {"tiles": red(overflow_l),
                          "assign": red(assign_ov_l),
                          "exchange": lax.psum(exchange_ov_l, all_axes)}
                if ex_budget_mat is not None:
                    # per-edge matrices: each "part" device owns row `me`
                    # (its send side); scatter into an (n, n) zeros and let
                    # the collective assemble the disjoint rows.  edges =
                    # total dropped per (src, dst) summed over replicas;
                    # demand = the in-step probe, the max overlap any
                    # (view, strip) replica saw on each edge.
                    em = lax.dynamic_update_slice(
                        jnp.zeros((n_data, n_data), jnp.int32),
                        exchange_ov_edges[None, :], (me, 0))
                    ov_out["exchange_edges"] = lax.psum(em, all_axes)
                    dm = lax.dynamic_update_slice(
                        jnp.zeros((n_data, n_data), jnp.int32),
                        exchange_demand_l[None, :], (me, 0))
                    dm = lax.psum(dm, data)
                    rest_axes = tuple(a for a in (pod, model, view) if a)
                    if rest_axes:
                        dm = lax.pmax(dm, rest_axes)
                    ov_out["exchange_demand"] = dm
                outs += (ov_out,)
            return outs
        return loss

    return shard_map(shard_fn, mesh=mesh, in_specs=in_specs,
                     out_specs=out_specs, check_vma=False)


# ---------------------------------------------------------------------------
# Distributed occupancy probe (tier-schedule telemetry)
# ---------------------------------------------------------------------------


def make_gs_probe(mesh, grid: TileGrid, *, k_tiers, views: Optional[int] = None,
                  assign_block: Optional[int] = None,
                  assign_impl: str = DEFAULT_ASSIGN_IMPL,
                  assign_budget: Optional[int] = None,
                  exchange: bool = False):
    """shard_map'd tier-schedule probe: (gaussians, cam) ->
    (tier_counts (n_tiers,) int32, max_occ () int32), REPLICATED.

    The distributed tiered forward bins each device's FOLDED
    ``(Vl * Pl * Tl,)`` flat tile axis (local views x local partitions x
    strip tiles), so tier caps must cover the worst such folded domain
    across the whole mesh — not the worst single view.  This probe runs the
    same project -> table all-gather -> view fold -> strip-local assignment
    pipeline as ``make_gs_forward`` at the ladder's Kmax, measures per-tile
    occupancy over the folded domain, counts tiles per desired tier
    (``core.tiling.tile_tiers`` over the FULL ladder), and pmax-reduces
    (counts, max occupancy) over every mesh axis.  The outputs are
    therefore identical on every device AND every host, which is what lets
    each process of a multi-host run feed them to
    ``TierSchedule.probe_counts`` independently and still compile the
    identical program — no out-of-band schedule broadcast needed.

    ``k_tiers`` must be the schedule's FULL ladder (``TierSchedule.ladder``:
    assignment runs at ladder[-1]; probing a trimmed ladder would under-
    measure).  The probe ignores ``strip_budget``/``gather_mode`` — it uses
    the exact f32 path, whose occupancy upper-bounds every budgeted
    variant, so caps sized here cover them too.  It DOES honor
    ``assign_impl``/``assign_budget``: the probe must measure occupancy
    with the same assignment the training step runs, or a budget-truncated
    step could be capped from un-truncated telemetry.

    ``exchange=True`` matches the sparse-exchange forward's binning domain:
    each device's window shrinks to its per-"part" sub-window of the strip
    (folded domain (Vl*Pl*sub,)), and the pmax makes every device agree on
    the worst sub-window.  The probe still builds its table via the full
    all-gather — occupancy of the complete table upper-bounds the
    budget-truncated exchange table, so caps sized here stay conservative
    regardless of the edge budget (and the probe needs no budget to exist
    yet; ``probe_gs_exchange`` sizes that knob independently).
    """
    ax = _axes(mesh)
    pod, data, model, view = ax
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_model = sizes.get(model, 1)
    n_view = sizes.get(view, 1)
    if views is None and n_view > 1:
        raise ValueError(
            f"mesh has a 'view' axis of size {n_view} but views=None; pass "
            f"views=V (a multiple of {n_view}) to probe the view-sharded "
            "domain")
    if views is not None and views % n_view:
        raise ValueError(f"views={views} must divide by the 'view' axis "
                         f"size {n_view}")
    vloc = views // n_view if views else None
    ladder = tuple(int(k) for k in k_tiers)
    K = ladder[-1]
    T = grid.n_tiles
    assert T % n_model == 0, (T, n_model)
    Tl = T // n_model
    n_data = sizes[data]
    sub = Tl
    pad = 0
    if exchange:
        sub = -(-Tl // n_data)                  # ceil: pad, never refuse
        pad = sub * n_data - Tl
    if assign_block is None:
        assign_block = max(1024, 4096 // vloc) if views else 4096

    g_spec = Gaussians(
        means=P(pod, data, None), log_scales=P(pod, data, None),
        quats=P(pod, data, None), opacity_logit=P(pod, data),
        colors=P(pod, data, None), active=P(pod, data), owner=P(pod, data),
    )
    vlead = (view,) if views else ()
    cam_spec = Camera(view=P(*vlead, None, None) if views else P(),
                      fx=P(*vlead) if views else P(),
                      fy=P(*vlead) if views else P(),
                      width=P(), height=P())
    lo_full, hi_full = tile_bounds(grid)
    lo_pad = hi_pad = None
    if exchange and pad:
        # padded per-strip rect tables (as in make_gs_forward): pad slots
        # get degenerate rects, so they bin zero occupancy
        lo_np, hi_np = np.asarray(lo_full), np.asarray(hi_full)
        lo_w = np.full((n_model * n_data * sub, 2), 1e9, np.float32)
        hi_w = np.full((n_model * n_data * sub, 2), -1e9, np.float32)
        for mi in range(n_model):
            lo_w[mi * n_data * sub: mi * n_data * sub + Tl] = \
                lo_np[mi * Tl: (mi + 1) * Tl]
            hi_w[mi * n_data * sub: mi * n_data * sub + Tl] = \
                hi_np[mi * Tl: (mi + 1) * Tl]
        lo_pad, hi_pad = jnp.asarray(lo_w), jnp.asarray(hi_w)
    nax = 2 if views else 1
    reduce_axes = tuple(a for a in (pod, data, model, view) if a)

    def shard_fn(g: Gaussians, cam: Camera):
        if views:
            splats = jax.vmap(lambda c: project(g, c),
                              in_axes=(CAM_VAXES,))(cam)
        else:
            splats = project(g, cam)
        aux_l = jnp.stack(
            [splats.mean2d[..., 0], splats.mean2d[..., 1],
             jnp.where(splats.valid, splats.radius, 0.0),
             splats.depth], axis=-1)                     # (Pl, Nl, 4)
        aux = lax.all_gather(aux_l, data, axis=nax, tiled=True)
        if views:
            aux = aux.reshape((-1,) + aux.shape[2:])     # fold Vl into Pl
        mean_g = aux[..., 0:2]
        radius_g = aux[..., 2]
        depth_g = aux[..., 3]
        valid_g = radius_g > 0

        if exchange:
            mi = lax.axis_index(model) if model is not None else 0
            pi = lax.axis_index(data)
            t0 = mi * Tl + pi * sub
            if pad:
                w0 = (mi * n_data + pi) * sub
                lo = lax.dynamic_slice_in_dim(lo_pad, w0, sub, 0)
                hi = lax.dynamic_slice_in_dim(hi_pad, w0, sub, 0)
            else:
                lo = lax.dynamic_slice_in_dim(lo_full, t0, sub, 0)
                hi = lax.dynamic_slice_in_dim(hi_full, t0, sub, 0)
        elif model is not None:
            mi = lax.axis_index(model)
            t0 = mi * Tl
            lo = lax.dynamic_slice_in_dim(lo_full, t0, Tl, 0)
            hi = lax.dynamic_slice_in_dim(hi_full, t0, Tl, 0)
        else:
            t0 = None
            lo, hi = lo_full, hi_full

        _, score, _ = _assign_tiles_local(mean_g, radius_g, depth_g, valid_g,
                                          lo, hi, K=K, block=assign_block,
                                          impl=assign_impl, grid=grid, t0=t0,
                                          tile_budget=assign_budget)
        occ = tile_occupancy(score).reshape(-1)   # (Vl*Pl*Tl,) or (..*sub,)
        tiers = tile_tiers(occ, ladder)
        counts = jnp.stack(
            [(tiers == i).sum() for i in range(len(ladder))]
        ).astype(jnp.int32)
        if reduce_axes:
            counts = lax.pmax(counts, reduce_axes)
            max_occ = lax.pmax(occ.max(), reduce_axes)
        else:
            max_occ = occ.max()
        return counts, max_occ

    return shard_map(shard_fn, mesh=mesh, in_specs=(g_spec, cam_spec),
                     out_specs=(P(), P()), check_vma=False)


def folded_tile_count(mesh, grid: TileGrid, n_parts: int,
                      views: Optional[int] = None,
                      exchange: bool = False) -> int:
    """Per-device flat tile count of the distributed binning domain,
    ``Vl * Pl * Tl`` — the cap clamp / ``note_overflow`` ``n_tiles``
    argument (binning over a domain of this size provably cannot drop).
    ``exchange=True`` shrinks the window to the per-"part" sub-window,
    ``Vl * Pl * ceil(Tl / n_data)``, matching the sparse-exchange step
    (which pads non-divisible strips)."""
    ax = _axes(mesh)
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    vloc = views // sizes.get(ax.view, 1) if views else 1
    t_loc = grid.n_tiles // sizes.get(ax.model, 1)
    if exchange:
        t_loc = -(-t_loc // sizes[ax.data])
    return vloc * (n_parts // sizes.get(ax.pod, 1)) * t_loc


@functools.lru_cache(maxsize=32)
def _gs_probe_jit(mesh, grid: TileGrid, ladder: tuple,
                  views: Optional[int],
                  assign_impl: str = DEFAULT_ASSIGN_IMPL,
                  assign_budget: Optional[int] = None,
                  exchange: bool = False):
    return jax.jit(make_gs_probe(mesh, grid, k_tiers=ladder, views=views,
                                 assign_impl=assign_impl,
                                 assign_budget=assign_budget,
                                 exchange=exchange))


def probe_gs_schedule(sched: TierSchedule, mesh, grid: TileGrid,
                      g: Gaussians, cam, *, views: Optional[int] = None,
                      assign_impl: str = DEFAULT_ASSIGN_IMPL,
                      assign_budget: Optional[int] = None,
                      exchange: bool = False):
    """Probe ``sched`` against the mesh: run the (cached, jitted)
    ``make_gs_probe`` telemetry reduction and update the schedule host-side
    via ``probe_counts``.  Returns the new ``(k_tiers, tier_caps)`` —
    identical on every host by construction (pmax'd telemetry).

    ``cam`` is one view-batch Camera (shaped for ``views``) or a sequence
    of them; with several, the per-tier counts are max-merged host-side so
    the caps cover the WORST probed batch of the step's exact folded
    domain.

    This is the shared probe for everything driving the distributed tiered
    step: ``fit_partitions`` calls it at init and after every densify
    (with two probe batches when the view batch is a single view), and
    benchmarks/table4_multinode.py sizes its swept steps with it.
    """
    cam_batches = [cam] if isinstance(cam, Camera) else list(cam)
    probe_fn = _gs_probe_jit(mesh, grid, tuple(sched.ladder), views,
                             assign_impl, assign_budget, exchange)
    counts, max_occ = None, 0
    for cb in cam_batches:
        c, m = probe_fn(g, cb)
        c = np.asarray(c)
        counts = c if counts is None else np.maximum(counts, c)
        max_occ = max(max_occ, int(m))
    n_parts = g.means.shape[0]
    return sched.probe_counts(
        counts, max_occ,
        n_tiles=folded_tile_count(mesh, grid, n_parts, views,
                                  exchange=exchange))


# ---------------------------------------------------------------------------
# Sparse-exchange edge budget: probe + schedule
# ---------------------------------------------------------------------------


def check_budget_matrix(budget, n_data: Optional[int] = None) -> np.ndarray:
    """Validate a per-edge exchange budget matrix LOUDLY.

    ``budget`` must be a square 2-D (n_part, n_part) array of edge budgets
    ``B[src, dst] >= 1``; with ``n_data`` given it must match the mesh's
    "part" axis size exactly (an undersized matrix would silently starve
    the missing edges, an oversized one would address devices that do not
    exist).  Returns the validated int64 numpy matrix.
    """
    B = np.asarray(budget)
    if B.ndim != 2 or B.shape[0] != B.shape[1]:
        raise ValueError(
            "exchange budget matrix must be square (n_part, n_part); got "
            f"shape {B.shape}")
    if n_data is not None and B.shape[0] != n_data:
        raise ValueError(
            f"exchange budget matrix is {B.shape[0]}x{B.shape[1]} but the "
            f"'part' axis has {n_data} devices — one row/column per device "
            "is required (undersized/oversized matrices are refused, never "
            "padded)")
    if not np.issubdtype(B.dtype, np.integer):
        if not np.all(B == np.floor(B)):
            raise ValueError("exchange budget matrix entries must be "
                             "integers")
    B = B.astype(np.int64)
    if (B < 1).any():
        raise ValueError(
            "exchange budget matrix entries must be >= 1 (every edge needs "
            f"at least one slot); min entry is {int(B.min())}")
    return B


def window_assignment(budget) -> np.ndarray:
    """Overlap-aware window assignment: which tile sub-window each "part"
    device renders, chosen from the per-edge budget matrix.

    The ragged ppermute ladder's wire cost is ``sum_k max_s B[s, tau[(s+k)
    % n]]`` — the per-shift slab is sized by the worst edge it carries, and
    shift 0 (each device keeping rows for its OWN window) is local, hence
    free.  With spatially compact (Morton-sorted) partitions each brick's
    overlap concentrates on a few screen bands, but the identity
    brick->band assignment scatters those heavy edges across every ring
    shift, so each slab pays a heavy max and the wire payload stops
    shrinking with n_part.  This routine returns a permutation ``tau``
    (``tau[i]`` = the band device ``i`` renders) that pulls each brick's
    dominant band onto the free local shift and packs the residue tightly:
    greedy dominant-band seeding (steepest brick first) refined by 2-opt
    swaps on the exact ladder objective.  Deterministic, pure numpy, a few
    ms at real part counts; the forward caches per budget matrix.
    """
    B = np.asarray(budget, np.int64)
    n = B.shape[0]
    if n <= 1:
        return np.zeros((n,), np.int64)
    shifts = [(np.arange(n) + k) % n for k in range(1, n)]

    def cost(tau):
        return sum(int(B[np.arange(n), tau[s]].max()) for s in shifts)

    tau = -np.ones(n, np.int64)
    used = np.zeros(n, bool)
    for s in np.argsort(-B.max(1), kind="stable"):
        d = int(np.argmax(np.where(used, -1, B[s])))
        tau[s] = d
        used[d] = True
    best = cost(tau)
    improved = True
    while improved:
        improved = False
        for i in range(n):
            for j in range(i + 1, n):
                t2 = tau.copy()
                t2[i], t2[j] = t2[j], t2[i]
                w = cost(t2)
                if w < best:
                    best, tau, improved = w, t2, True
    ident = np.arange(n, dtype=np.int64)
    return tau if best < cost(ident) else ident


class ExchangeSchedule:
    """Telemetry-driven per-(src, dst) edge budget for the sparse exchange.

    The exchange packs, per destination, the local splats overlapping that
    destination's sub-window into a static number of slots.  Like the tier
    caps, the budget is a STATIC shape fed from concrete telemetry and
    guarded by a psum'd overflow counter — the same probe/overflow honesty
    contract.  ``budget`` is either one scalar edge budget (every edge
    packs the same slot count — the legacy shape) or an (n_part, n_part)
    int matrix ``B[src, dst]`` (per-edge: spatially distant shard pairs
    get small budgets, neighbours get large ones — the shape that scales
    with n_part; see ``probe_gs_exchange(per_edge=True)``):

      probe_budget(max_edge, n_local)   size the budget from the pmax'd
          worst overlap count — a scalar (worst edge anywhere) or an
          (n, n) demand matrix (worst per edge) — scaled by ``slack`` and
          rounded so nearby probes hash to the same jit entry; clamped to
          ``n_local`` (a source can never send more splats than it holds,
          so overflow is impossible at the clamp).
      note_overflow(ov, n_local)        a step reported dropped splats: the
          budget grows geometrically (clamped at ``n_local``).  With a
          matrix budget and the step's psum'd per-edge counter matrix,
          ONLY the starved edges grow — a congested neighbour edge never
          inflates the whole table.  Returns True when it changed —
          rebuild the step.  Never silent truncation: every dropped splat
          shows up in the counter first.
      ensure(demand, n_local)           grow (never shrink) the budget to
          cover a demand measured IN-STEP (the forward's pmax'd
          ``"exchange_demand"`` matrix) — the no-host-round-trip resize
          ``fit_partitions`` uses after densify.
      state_dict / load_state           checkpointed via the manager's
          ``extra`` payload so a resumed run keeps its probed budget
          instead of re-probing (matrices ride as nested lists).
    """

    def __init__(self, *, slack: float = 1.5, round_to: int = 16,
                 growth: float = 2.0, budget=None):
        self.slack = float(slack)
        self.round_to = int(round_to)
        self.growth = float(growth)
        self.budget = self._coerce(budget)

    def _coerce(self, budget):
        if budget is None:
            return None
        if np.ndim(budget) == 0:
            return int(budget)
        return check_budget_matrix(budget)

    def _sized(self, demand, n_local: int) -> np.ndarray:
        """slack -> round_to -> [1, n_local] clamp, elementwise."""
        b = np.ceil(np.maximum(np.asarray(demand, np.int64), 1)
                    * self.slack).astype(np.int64)
        b = -(-b // self.round_to) * self.round_to
        return np.clip(b, 1, int(n_local))

    def probe_budget(self, max_edge, n_local: int):
        """Size the edge budget from the pmax'd worst overlap count: a
        scalar count -> scalar budget, an (n, n) per-edge demand matrix ->
        per-edge budget matrix."""
        if np.ndim(max_edge) == 2:
            self.budget = check_budget_matrix(
                self._sized(np.asarray(max_edge), n_local))
            return self.budget
        self.budget = int(self._sized(int(max_edge), n_local))
        return self.budget

    def note_overflow(self, overflow, n_local: int) -> bool:
        """React to a step's dropped-splat counter: grow the budget by
        ``growth`` (clamped at ``n_local``, where overflow is impossible).
        With a matrix budget and a matching (n, n) counter, only the
        starved edges grow.  Returns True when it changed — rebuild the
        step."""
        if self.budget is None:
            return False
        ov = np.asarray(overflow)
        if np.ndim(self.budget) == 2:
            B = np.asarray(self.budget)
            starved = (ov > 0) if ov.shape == B.shape \
                else np.full(B.shape, int(ov.sum()) > 0)
            if not starved.any():
                return False
            grown = np.minimum(
                int(n_local),
                np.maximum(self.round_to,
                           np.ceil(B * self.growth).astype(np.int64)))
            new = np.where(starved, np.maximum(B, grown), B)
            if (new == B).all():
                return False
            self.budget = new
            return True
        if int(ov.sum()) <= 0:
            return False
        grown = min(int(n_local),
                    max(self.round_to, int(np.ceil(self.budget
                                                   * self.growth))))
        if grown <= self.budget:
            return False
        self.budget = grown
        return True

    def ensure(self, demand, n_local: int) -> bool:
        """Grow (never shrink) the budget to cover ``demand`` splats per
        edge — rounded to ``round_to``, clamped at ``n_local``.  This is
        the in-step resize path: ``fit_partitions`` feeds it the running
        max of the step's own pmax'd demand matrix (plus the densify
        growth bound), so budget growth needs no host probe round-trip.
        Returns True when the budget changed — rebuild the step."""
        if self.budget is None:
            return False
        d = np.maximum(np.asarray(demand, np.int64), 1)
        need = np.clip(-(-d // self.round_to) * self.round_to,
                       1, int(n_local))
        if np.ndim(self.budget) == 2:
            need = check_budget_matrix(need, np.asarray(self.budget).shape[0])
            new = np.maximum(np.asarray(self.budget), need)
            if (new == np.asarray(self.budget)).all():
                return False
            self.budget = new
            return True
        new = max(int(self.budget), int(need))
        if new == self.budget:
            return False
        self.budget = new
        return True

    def budget_key(self):
        """Hashable snapshot of the budget (int or tuple-of-tuples) — the
        jit/step-cache key for the static exchange shapes."""
        if self.budget is None or np.ndim(self.budget) == 0:
            return self.budget
        return tuple(tuple(int(x) for x in row)
                     for row in np.asarray(self.budget))

    def state_dict(self) -> dict:
        """JSON-able snapshot, stored under CheckpointManager extra
        ["exchange"] by ``fit_partitions``.  A matrix budget serializes as
        nested lists."""
        b = self.budget
        if b is not None and np.ndim(b) == 2:
            b = [[int(x) for x in row] for row in np.asarray(b)]
        return {"slack": self.slack, "round_to": self.round_to,
                "growth": self.growth, "budget": b}

    def load_state(self, state: dict) -> "ExchangeSchedule":
        """Restore a snapshot IN PLACE (the checkpoint wins) — a resumed
        run keeps its probed/grown budget without re-probing.  Matrix
        budgets are validated loudly (``check_budget_matrix``)."""
        self.slack = float(state["slack"])
        self.round_to = int(state["round_to"])
        self.growth = float(state["growth"])
        self.budget = self._coerce(state["budget"])
        return self

    @classmethod
    def from_state(cls, state: dict) -> "ExchangeSchedule":
        """Rebuild a schedule from a ``state_dict`` snapshot."""
        return cls().load_state(state)

    def __repr__(self):
        b = self.budget
        if b is not None and np.ndim(b) == 2:
            B = np.asarray(b)
            b = (f"{B.shape[0]}x{B.shape[1]}"
                 f"[{int(B.min())}..{int(B.max())}]")
        return (f"ExchangeSchedule(budget={b}, "
                f"slack={self.slack}, round_to={self.round_to})")


def make_gs_exchange_probe(mesh, grid: TileGrid, *,
                           views: Optional[int] = None,
                           per_edge: bool = False):
    """(gaussians, cam) -> exchange-overlap telemetry, REPLICATED — what
    ``ExchangeSchedule.probe_budget`` sizes the edge budget(s) from.

    Each device projects its local splats and counts, per destination
    sub-window, how many overlap (``window_overlap_mask`` — the exchange's
    exact packing predicate, so the count is the exact slot demand).
    ``per_edge=False`` returns the () int32 WORST count over every edge,
    pmax'd over every mesh axis; ``per_edge=True`` returns the full
    (n_part, n_part) int32 demand matrix — row ``s`` is what partition
    ``s`` must send to each destination's sub-window, assembled by a psum
    of disjoint rows over "part" and pmax'd over the remaining axes.
    Either way all hosts agree on the result and land on the identical
    budget.  No collective moves table data — the probe is cheaper than
    one gather step.  A strip that does not divide by the "part" axis is
    padded exactly like the forward (pad sub-windows count nothing).
    """
    ax = _axes(mesh)
    pod, data, model, view = ax
    sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    n_model = sizes.get(model, 1)
    n_data = sizes[data]
    n_view = sizes.get(view, 1)
    if views is not None and views % n_view:
        raise ValueError(f"views={views} must divide by the 'view' axis "
                         f"size {n_view}")
    if views is None and n_view > 1:
        raise ValueError(f"mesh has a 'view' axis of size {n_view} but "
                         "views=None; pass views=V")
    T = grid.n_tiles
    assert T % n_model == 0, (T, n_model)
    Tl = T // n_model
    sub = -(-Tl // n_data)                      # ceil: pad, never refuse
    pad = sub * n_data - Tl

    g_spec = Gaussians(
        means=P(pod, data, None), log_scales=P(pod, data, None),
        quats=P(pod, data, None), opacity_logit=P(pod, data),
        colors=P(pod, data, None), active=P(pod, data), owner=P(pod, data),
    )
    vlead = (view,) if views else ()
    cam_spec = Camera(view=P(*vlead, None, None) if views else P(),
                      fx=P(*vlead) if views else P(),
                      fy=P(*vlead) if views else P(),
                      width=P(), height=P())
    reduce_axes = tuple(a for a in (pod, data, model, view) if a)

    def shard_fn(g: Gaussians, cam: Camera):
        if views:
            splats = jax.vmap(lambda c: project(g, c),
                              in_axes=(CAM_VAXES,))(cam)
        else:
            splats = project(g, cam)
        mx = splats.mean2d[..., 0]
        my = splats.mean2d[..., 1]
        rad = jnp.where(splats.valid, splats.radius, 0.0)
        val = splats.valid
        if views:  # fold Vl into the partition axis: (Vl*Pl, Nl)
            fold = lambda x: x.reshape((-1,) + x.shape[2:])
            mx, my, rad, val = fold(mx), fold(my), fold(rad), fold(val)
        base = lax.axis_index(model) * Tl if model is not None else 0
        t0_all = base + jnp.arange(n_data, dtype=jnp.int32) * sub
        hit = window_overlap_mask(mx, my, rad, val, grid,
                                  t0=t0_all, n_local=sub,
                                  t_end=(base + Tl) if pad else None)
        counts = hit.sum(-1, dtype=jnp.int32)    # (n_data, R)
        if per_edge:
            row = counts.max(1)                  # my demand toward each dst
            dm = lax.dynamic_update_slice(
                jnp.zeros((n_data, n_data), jnp.int32),
                row[None, :], (lax.axis_index(data), 0))
            dm = lax.psum(dm, data)
            rest_axes = tuple(a for a in (pod, model, view) if a)
            return lax.pmax(dm, rest_axes) if rest_axes else dm
        m = counts.max()
        return lax.pmax(m, reduce_axes) if reduce_axes else m

    return shard_map(shard_fn, mesh=mesh, in_specs=(g_spec, cam_spec),
                     out_specs=P(), check_vma=False)


@functools.lru_cache(maxsize=32)
def _gs_exchange_probe_jit(mesh, grid: TileGrid, views: Optional[int],
                           per_edge: bool = False):
    return jax.jit(make_gs_exchange_probe(mesh, grid, views=views,
                                          per_edge=per_edge))


def probe_gs_exchange(esched: ExchangeSchedule, mesh, grid: TileGrid,
                      g: Gaussians, cam, *,
                      views: Optional[int] = None, per_edge: bool = False):
    """Probe ``esched`` against the mesh: measure the worst per-edge
    overlap over one or more view batches (max-merged host-side, like
    ``probe_gs_schedule``) and size the edge budget.  ``per_edge=True``
    probes the full (n_part, n_part) demand matrix and sizes a matrix
    budget.  Returns the new budget — identical on every host (pmax'd /
    psum'd-disjoint telemetry)."""
    cam_batches = [cam] if isinstance(cam, Camera) else list(cam)
    probe_fn = _gs_exchange_probe_jit(mesh, grid, views, per_edge)
    if per_edge:
        mx = None
        for cb in cam_batches:
            got = np.asarray(probe_fn(g, cb))
            mx = got if mx is None else np.maximum(mx, got)
    else:
        mx = 0
        for cb in cam_batches:
            mx = max(mx, int(probe_fn(g, cb)))
    ax = _axes(mesh)
    n_data = dict(zip(mesh.axis_names, mesh.devices.shape))[ax.data]
    n_local = g.means.shape[1] // n_data
    return esched.probe_budget(mx, n_local)


# ---------------------------------------------------------------------------
# Distributed train step
# ---------------------------------------------------------------------------


#: sentinel: "no explicit k_tiers argument — resolve from the train cfg"
_FROM_CFG = object()


def make_gs_train_step(mesh, cfg: GSTrainCfg, grid: TileGrid, extent: float,
                       *, impl: str = "auto", views: Optional[int] = None,
                       assign_block: Optional[int] = None,
                       k_tiers=_FROM_CFG,
                       tier_caps: Optional[tuple] = None,
                       return_overflow: bool = False, win_size: int = 7,
                       assign_impl=_FROM_CFG, assign_budget=_FROM_CFG,
                       exchange=_FROM_CFG, exchange_budget=_FROM_CFG):
    """jit'd (gaussians, opt, batch) -> (gaussians, opt, loss).

    Per-partition losses are averaged globally, but gradients never mix
    partitions (each gaussian belongs to exactly one P slice): the paper's
    independent-training semantics inside one SPMD program.

    views=V runs the minibatch-of-views step: batch["gt_tiles"] is
    (V, P*T, 3, th, tw), batch["cam"] carries (V, 4, 4) views, and the loss
    (hence the gradient) averages over the view batch.  On a mesh with a
    "view" axis the batch's leading V dim is sharded over it (see
    make_gs_forward / gs_shardings).

    Rasterization defaults to OCCUPANCY TIERS: ``k_tiers`` left unset pulls
    ``cfg.resolved_k_tiers()`` (the trainer-wide default schedule; set
    ``cfg.dense_k=`` to escape back to dense-K rasterization).  An explicit
    ``k_tiers=None`` forces dense, an explicit tuple pins the ladder.
    ``tier_caps=None`` uses the always-exact strip-sized caps — correct but
    unmeasured; production drives this factory through a
    ``core.tiling.TierSchedule`` (probe -> train -> densify -> re-probe)
    and passes ``(schedule.k_tiers, schedule.tier_caps)``.  cfg.K (or
    cfg.dense_k) is the dense path's assignment depth.

    ``return_overflow=True`` makes the step return
    ``(gaussians, opt, loss, overflow)`` where overflow is a dict of
    globally psum'd () int32 counters — ``"tiles"`` (tiered dropped tiles,
    for ``TierSchedule.note_overflow``), ``"assign"`` (sorted-assignment
    budget truncation, grows ``assign_budget``) and ``"exchange"``
    (sparse-exchange dropped splats, for ``ExchangeSchedule.note_overflow``)
    — the telemetry the ``fit_partitions`` driver consumes, mirroring
    train.make_train_step.  ``win_size`` is the per-tile D-SSIM window
    (see make_gs_forward).

    ``exchange``/``exchange_budget`` (default: from cfg) select the
    sparse-overlap table exchange instead of the full all-gather — see
    make_gs_forward.

    ``cfg.dtype_policy="bf16"`` runs the forward/backward with bf16 wire
    tables (see make_gs_forward); the Adam state, loss and every update
    stay f32 under every policy.

    ``cfg.grad_compress != "none"`` wires optim.compress.compress_grads
    over the per-partition gradient tree (quantise→dequantise with error
    feedback, Seide et al. practice) and CHANGES THE STEP SIGNATURE to
    ``step(g, opt, err, batch) -> (g, opt, err, loss[, overflow])``: the
    error-feedback tree (zeros-like the trainables for "int8"; None for
    the stateless "bf16") is carried by the caller across steps — and
    through checkpoints by ``fit_partitions``.  With the default "none"
    the signature, donation pattern and compiled program are exactly the
    pre-knob ones.
    """
    check_auto_mesh(mesh)
    if k_tiers is _FROM_CFG:
        k_tiers = cfg.resolved_k_tiers()
    if assign_impl is _FROM_CFG:
        assign_impl = cfg.assign_impl
    if assign_budget is _FROM_CFG:
        assign_budget = cfg.assign_budget
    if exchange is _FROM_CFG:
        exchange = cfg.exchange
    if exchange_budget is _FROM_CFG:
        exchange_budget = cfg.exchange_budget
    lrs = group_lrs(cfg, extent)
    g_sh, opt_sh, b_sh = gs_shardings(mesh, views=views)
    fwd = make_gs_forward(mesh, grid, K=cfg.assign_K, impl=impl,
                          lambda_dssim=cfg.lambda_dssim,
                          gather_mode=cfg.gather_mode,
                          strip_budget=cfg.strip_budget, views=views,
                          assign_block=assign_block,
                          k_tiers=k_tiers, tier_caps=tier_caps,
                          return_overflow=return_overflow, win_size=win_size,
                          assign_impl=assign_impl,
                          assign_budget=assign_budget,
                          exchange=exchange, exchange_budget=exchange_budget,
                          dtype_policy=cfg.dtype_policy)

    def loss_fn(tr, g, cam, gt, mask):
        out = fwd(g.with_trainable(tr), cam, gt, mask)
        if return_overflow:
            return out
        z = jnp.zeros((), jnp.int32)
        return out, {"tiles": z, "assign": z, "exchange": z}

    compress = cfg.grad_compress

    @scope("adam")
    def adam(g: Gaussians, opt: GSOptState, grads, loss, overflow):
        s = opt.step + 1
        bc1 = 1.0 - cfg.b1 ** s.astype(jnp.float32)
        bc2 = 1.0 - cfg.b2 ** s.astype(jnp.float32)
        tr = g.trainable()
        new_tr, new_m, new_v = {}, {}, {}
        for k in tr:
            gr = grads[k].astype(jnp.float32)
            m = cfg.b1 * opt.m[k] + (1 - cfg.b1) * gr
            v = cfg.b2 * opt.v[k] + (1 - cfg.b2) * gr * gr
            d = (m / bc1) / (jnp.sqrt(v / bc2) + cfg.eps)
            new_tr[k] = (tr[k] - lrs[k] * d).astype(tr[k].dtype)
            new_m[k], new_v[k] = m, v
        gnorm = jnp.linalg.norm(grads["means"].astype(jnp.float32), axis=-1)
        new_opt = GSOptState(new_m, new_v, s,
                             opt.grad_accum + gnorm,
                             opt.grad_count + (gnorm > 0))
        return g.with_trainable(new_tr), new_opt, loss, overflow

    def step(g: Gaussians, opt: GSOptState, batch):
        (loss, overflow), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            g.trainable(), g, batch["cam"], batch["gt_tiles"],
            batch["mask_tiles"])
        g, opt, loss, overflow = adam(g, opt, grads, loss, overflow)
        out = (g, opt, loss)
        return out + (overflow,) if return_overflow else out

    def step_compressed(g: Gaussians, opt: GSOptState, err, batch):
        (loss, overflow), grads = jax.value_and_grad(loss_fn, has_aux=True)(
            g.trainable(), g, batch["cam"], batch["gt_tiles"],
            batch["mask_tiles"])
        grads = jax.tree.map(lambda x: x.astype(jnp.float32), grads)
        grads, err, _ = compress_grads(grads, compress, err)
        g, opt, loss, overflow = adam(g, opt, grads, loss, overflow)
        out = (g, opt, err, loss)
        return out + (overflow,) if return_overflow else out

    rep = NamedSharding(mesh, P())
    ov_sh = {"tiles": rep, "assign": rep, "exchange": rep}
    if exchange and exchange_budget is not None \
            and np.ndim(exchange_budget) == 2:
        # matrix budgets add the per-edge counters (replicated matrices)
        ov_sh["exchange_edges"] = rep
        ov_sh["exchange_demand"] = rep
    if compress == "none":
        out_sh = (g_sh, opt_sh, rep) + ((ov_sh,) if return_overflow else ())
        return jax.jit(
            step,
            in_shardings=(g_sh, opt_sh, b_sh),
            out_shardings=out_sh,
            donate_argnums=(0, 1),
        )
    # err tree shards like the Adam moments (same trainables structure);
    # the stateless "bf16" mode carries err=None (an empty pytree) through
    # the same signature so both compressed modes share one calling shape
    err_sh = opt_sh.m if compress == "int8" else None
    out_sh = (g_sh, opt_sh, err_sh, rep) \
        + ((ov_sh,) if return_overflow else ())
    return jax.jit(
        step_compressed,
        in_shardings=(g_sh, opt_sh, err_sh, b_sh),
        out_shardings=out_sh,
        donate_argnums=(0, 1, 2),
    )


# ---------------------------------------------------------------------------
# Dry-run input specs (ShapeDtypeStruct stand-ins, no allocation)
# ---------------------------------------------------------------------------


def gs_state_specs(n_parts: int, n_gaussians: int):
    """Gaussian + opt state ShapeDtypeStructs for the (P, N) batched layout.

    Shapes are GLOBAL (pre-sharding): pair with ``gs_shardings`` to get the
    device layout — leading P sharded over "pod", N over "part"/"data",
    replicated along "model" and "view" (every device needs the full local
    gaussian shard to project its own views/strips).
    """
    Pn, N = n_parts, n_gaussians
    f32 = jnp.float32
    g = Gaussians(
        means=jax.ShapeDtypeStruct((Pn, N, 3), f32),
        log_scales=jax.ShapeDtypeStruct((Pn, N, 3), f32),
        quats=jax.ShapeDtypeStruct((Pn, N, 4), f32),
        opacity_logit=jax.ShapeDtypeStruct((Pn, N), f32),
        colors=jax.ShapeDtypeStruct((Pn, N, 3), f32),
        active=jax.ShapeDtypeStruct((Pn, N), jnp.bool_),
        owner=jax.ShapeDtypeStruct((Pn, N), jnp.int32),
    )
    tr = {k: getattr(g, k) for k in
          ("means", "log_scales", "quats", "opacity_logit", "colors")}
    opt = GSOptState(
        m=dict(tr), v=dict(tr),
        step=jax.ShapeDtypeStruct((), jnp.int32),
        grad_accum=jax.ShapeDtypeStruct((Pn, N), f32),
        grad_count=jax.ShapeDtypeStruct((Pn, N), f32),
    )
    return g, opt


def gs_batch_specs(n_parts: int, grid: TileGrid,
                   views: Optional[int] = None):
    """Batch ShapeDtypeStructs for the flat-tile (P*T, ...) layout.

    Shapes are GLOBAL: with ``views=V`` the leading V axis is what a mesh's
    "view" axis shards (V must divide it) and the flat (P*T,) tile axis is
    what ("pod", "model") shard; without views the V axis is absent.
    cam.view is (V, 4, 4) ("view"-sharded alongside gt/mask), width/height
    stay replicated scalars.
    """
    T = grid.n_tiles
    f32 = jnp.float32
    vlead = (views,) if views else ()
    return {
        "gt_tiles": jax.ShapeDtypeStruct(
            vlead + (n_parts * T, 3, grid.tile_h, grid.tile_w), f32),
        "mask_tiles": jax.ShapeDtypeStruct(
            vlead + (n_parts * T, grid.tile_h, grid.tile_w), jnp.bool_),
        "cam": Camera(
            view=jax.ShapeDtypeStruct(vlead + (4, 4), f32),
            fx=jax.ShapeDtypeStruct(vlead, f32),
            fy=jax.ShapeDtypeStruct(vlead, f32),
            width=jax.ShapeDtypeStruct((), jnp.int32),
            height=jax.ShapeDtypeStruct((), jnp.int32),
        ),
    }


# ---------------------------------------------------------------------------
# Distributed schedule driver (host loop)
# ---------------------------------------------------------------------------


def _tile_view_batches(gts, masks, grid: TileGrid):
    """Per-partition images -> the distributed flat-tile batch layout.

    gts (P, V, H, W, 3), masks (P, V, H, W) bool or None ->
    (gt_tiles (V, P*T, 3, th, tw), mask_tiles (V, P*T, th, tw)) as host
    numpy arrays (sliced per minibatch by the driver).  masks=None means
    "every IMAGE pixel counts" — grid padding rows/columns (a resolution
    that isn't a tile multiple) are still masked OFF, matching the
    single-device full-image loss, which never sees pad pixels."""
    Pn, V = gts.shape[:2]
    tiler = jax.jit(jax.vmap(jax.vmap(partial(tile_image, grid=grid))))
    gt_t = np.asarray(tiler(jnp.asarray(gts)))           # (P, V, T, 3, th, tw)
    gt_t = gt_t.transpose(1, 0, 2, 3, 4, 5).reshape(
        (V, Pn * grid.n_tiles) + gt_t.shape[3:])
    if masks is None:
        masks = jnp.ones((Pn, V) + gts.shape[2:4], jnp.float32)
    mask_t = np.asarray(
        tiler(jnp.asarray(masks)[..., None].astype(jnp.float32)))
    mask_t = (mask_t.transpose(1, 0, 2, 3, 4, 5)[:, :, :, 0]
              .reshape((V, Pn * grid.n_tiles) + mask_t.shape[4:]) > 0.5)
    return gt_t, mask_t


def rebalance_partitions(g: Gaussians, opt: GSOptState, mesh, *,
                         threshold: float = 1.5):
    """Host-side dynamic load rebalance for the sparse exchange: permute
    each partition's rows so LIVE splats spread evenly over the "part"
    shards of the equal-capacity (P, N) stacks.

    Densify/prune is data-dependent, so per-shard live counts drift apart
    over training; under ``exchange=True`` a crowded shard both sends and
    rasterizes more than its peers (the gather path is insensitive — every
    device holds the full table either way).  When the worst shard's live
    count exceeds ``threshold`` x the partition mean, live rows are dealt
    in CONTIGUOUS near-equal blocks across shards (a pure PERMUTATION of
    rows — capacities, shapes and jit caches are untouched; no reshard,
    no recompile).  Contiguous dealing preserves the Morton row order the
    overlap-aware partitioning established (partition.spatial_order):
    each shard stays a compact spatial brick, which is what keeps the
    probed per-edge exchange budgets small — a round-robin deal would
    re-scramble every shard back to ~uniform overlap.  ``threshold=0.0``
    forces the permutation unconditionally (tests).

    Optimizer rows (m/v/grad accumulators) travel with their splats, so
    training is equivalent up to row order: assignment top-k breaks ties by
    row index, so a scene with tie-free scores composites identically and
    the loss trajectory is bit-stable (see tests/test_distributed.py).

    Returns ``(g, opt, moved)`` with host (numpy) leaves when ``moved`` —
    callers re-``device_put`` onto their shardings — or the inputs
    untouched when the skew is under threshold.
    """
    ax = _axes(mesh)
    n_data = dict(zip(mesh.axis_names, mesh.devices.shape))[ax.data]
    gh = jax.device_get(g)
    oh = jax.device_get(opt)
    active = np.asarray(gh.active)
    Pn, N = active.shape
    Nl = N // n_data
    shard_live = active.reshape(Pn, n_data, Nl).sum(-1)
    skew = shard_live.max(-1) / np.maximum(shard_live.mean(-1), 1.0)
    if float(skew.max()) <= threshold:
        return g, opt, False
    # stable live-first order, dealt in contiguous blocks: the live rows
    # (which keep their Morton order) split into n_data near-equal chunks
    # — chunk i fills the front of shard i, dead rows fill the leftover
    # slots.  Every shard gets within one of the same live count, each
    # chunk is a contiguous (spatially compact) run, and equal inputs
    # produce the identical permutation on every host (numpy stable sort,
    # no RNG).
    perm = np.empty((Pn, N), np.int64)
    for p in range(Pn):
        order = np.argsort(~active[p], kind="stable")
        L = int(active[p].sum())
        szs = np.full(n_data, L // n_data, np.int64)
        szs[: L % n_data] += 1
        starts = np.concatenate([[0], np.cumsum(szs)[:-1]])
        dest = np.empty(N, np.int64)
        for i in range(n_data):
            dest[starts[i]: starts[i] + szs[i]] = i * Nl + np.arange(szs[i])
        dest[L:] = np.concatenate(
            [np.arange(i * Nl + szs[i], (i + 1) * Nl)
             for i in range(n_data)])
        perm[p, dest] = order

    def take(x):
        x = np.asarray(x)
        if x.ndim >= 2 and x.shape[:2] == (Pn, N):
            return np.stack([x[p][perm[p]] for p in range(Pn)])
        return x

    return jax.tree.map(take, gh), jax.tree.map(take, oh), True


def _put_unaliased(tree, shardings):
    """``jax.device_put`` that never returns the caller's own buffers.

    The train step donates its state.  A device_put onto devices an array
    already lives on may hand back that array's buffers (even with
    ``may_alias=False`` for a replicated sharding), and the first step would
    then delete the caller's arrays.  Only leaves that came back aliased are
    copied, so a scene split over other devices is not duplicated."""
    def put(x, sh):
        y = jax.device_put(x, sh)
        if isinstance(x, jax.Array):
            held = {s.data.unsafe_buffer_pointer()
                    for s in x.addressable_shards}
            if any(s.data.unsafe_buffer_pointer() in held
                   for s in y.addressable_shards):
                y = jax.device_put(jnp.copy(x), sh)
        return y
    return jax.tree.map(put, tree, shardings)


def fit_partitions(g: Gaussians, cams: Camera, gts, masks, cfg: GSTrainCfg,
                   *, mesh, steps: int, extent: float, key=None,
                   densify_every: int = 0, densify_from: int = 100,
                   grid: Optional[TileGrid] = None,
                   view_batch: Optional[int] = None,
                   schedule: Optional[TierSchedule] = None,
                   impl: str = "auto", win_size: int = 7,
                   rebalance_every: int = 0,
                   rebalance_threshold: float = 1.5,
                   ckpt=None, ckpt_every: int = 0, log_every: int = 0,
                   warm_start=None, densify_cap: Optional[int] = None,
                   exchange_schedule=None):
    """Distributed tier-schedule driver: train every partition of the
    batched (P, N) layout in ONE SPMD program on ``mesh``, running the same
    probe -> train -> densify -> re-probe lifecycle as the single-device
    ``train.fit_partition``.

    g: (P, N, ...) batched Gaussians (host or device); gts (P, V, H, W, 3)
    per-partition GT images; masks (P, V, H, W) bool or None.  Each step
    consumes ``view_batch`` consecutive views (default cfg.view_batch; the
    minibatch is sharded over the mesh's "view" axis, so it must divide by
    that axis' size).  Returns (g, opt, losses) with the state still
    device-sharded per ``gs_shardings``.

    Tier-schedule lifecycle (tiered-by-default; ``cfg.dense_k=`` opts out):
    the schedule is probed through ``probe_gs_schedule`` — occupancy over
    each device's folded (Vl*T,) binning domain, pmax-reduced across the
    mesh so every host lands on the same cap ladder — the step trains with
    its static (k_tiers, tier_caps) and reports the psum'd overflow
    counter, any overflow grows the caps (bounded recompile), and every
    densify event (vmapped over partitions inside jit) re-probes.

    Sparse exchange (``cfg.exchange=True``): the step swaps the table
    all-gather for the budgeted sparse exchange.  The budget comes from
    ``cfg.exchange_budget`` when set (pinned — never re-probed), else from
    an ``ExchangeSchedule`` probed PER EDGE at init (a full (n, n) demand
    matrix whenever the "part" axis has more than one shard, so each
    (src, dst) pair gets its own budget); a starved edge surfaces in the
    psum'd ``"exchange_edges"`` counter and grows geometrically — only
    that edge, bounded recompile, never silent truncation.  The step's
    pmax'd ``"exchange_demand"`` matrix is the IN-STEP probe: the driver
    keeps its running max and resizes budgets after densify via
    ``ExchangeSchedule.ensure`` (demand + cfg.max_new upper-bounds the
    post-densify overlap) with no host probe round-trip; only a rebalance
    — which re-deals rows across shards — still re-probes on the host.
    ``rebalance_every=R`` additionally checks per-shard live-splat skew
    every R steps and deals live rows in contiguous Morton-preserving
    blocks across the "part" shards when it passes
    ``rebalance_threshold`` (see ``rebalance_partitions``; works with or
    without exchange).

    Checkpoint/resume: with ``ckpt`` (a runtime.CheckpointManager) the
    driver restores the newest complete (g, opt) checkpoint, loads the
    TierSchedule state saved alongside it (``extra["schedule"]``) — so a
    resumed run keeps its probed caps instead of re-probing from scratch —
    plus the exchange-budget state (``extra["exchange"]``, same contract:
    restored budgets are NOT re-probed), fast-forwards the densify key
    stream, and continues from that step; ``ckpt_every`` saves (g, opt) +
    schedules periodically and a final checkpoint always lands at
    ``steps``.  ``losses`` covers only the steps this call actually ran.

    Warm start (timeseries): ``warm_start=(state_tree, extra, step)`` is an
    in-memory resume — ``state_tree`` is a ``(g, opt[, err])`` host tree,
    ``extra`` the checkpoint-extra dict whose ``schedule``/``exchange``
    states are loaded (so init probes are SKIPPED, same contract as a disk
    resume), and ``step`` the global step the seed was saved at (the caller
    passes ``steps = step + n`` to run n more).  The int8 error-feedback
    residual is always re-zeroed at the boundary.  A restorable on-disk
    checkpoint takes precedence.  ``densify_cap=`` bounds the LIVE splat
    count per partition during densify (see ``GSTrainCfg.densify_cap``).

    Tracing (``core.trace``): each iteration is the profiler step
    ``gs.fit.step`` and holds the host spans ``gs.fit.put`` (minibatch
    put), ``gs.fit.build`` (a new step program; its first dispatch
    compiles), ``gs.fit.dispatch``, ``gs.fit.sync`` (the loss read),
    ``gs.fit.schedule`` (overflow counters), ``gs.fit.densify`` (densify,
    re-probes, rebalance) and ``gs.fit.ckpt``.
    """
    check_auto_mesh(mesh)
    if grid is None:
        grid = TileGrid(cams.width, cams.height, cfg.tile_h, cfg.tile_w)
    if key is None:
        key = jax.random.PRNGKey(0)
    Pn = g.means.shape[0]
    V = gts.shape[1]
    vb = max(1, min(view_batch or cfg.view_batch, V))
    sched = schedule if schedule is not None else cfg.tier_schedule()
    m_dev = folded_tile_count(mesh, grid, Pn, views=vb,
                              exchange=cfg.exchange)
    # exchange_schedule= mirrors schedule=: the caller keeps the handle, so
    # a timeseries driver can carry probed/grown budgets across timesteps
    ex = exchange_schedule if exchange_schedule is not None else (
        ExchangeSchedule(budget=cfg.exchange_budget) if cfg.exchange
        else None)
    ex_pinned = cfg.exchange_budget is not None
    n_data = dict(zip(mesh.axis_names, mesh.devices.shape))[_axes(mesh).data]
    Nl = g.means.shape[1] // n_data
    # per-edge budgets need a real "part" axis (a 1x1 matrix is a scalar)
    ex_per_edge = cfg.exchange and not ex_pinned and n_data > 1

    gt_tiles, mask_tiles = _tile_view_batches(gts, masks, grid)
    g_sh, opt_sh, b_sh = gs_shardings(mesh, views=vb)
    opt = init_opt(g)       # layout-polymorphic: (P, N) accumulators here

    # grad-compress error feedback (optim/compress.py): int8 carries a
    # residual tree shaped like the trainables; "bf16" is stateless (err
    # stays None through the compressed step's uniform signature); "none"
    # keeps the original (g, opt, batch) step untouched
    compress = cfg.grad_compress
    err = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32),
                       g.trainable()) if compress == "int8" else None
    err_sh = opt_sh.m if compress == "int8" else None

    def state_tree(gg, oo, ee):
        # the int8 residual RIDES THE CHECKPOINT (it is step state: dropping
        # it on resume would silently re-inject the accumulated error)
        return (gg, oo, ee) if compress == "int8" else (gg, oo)

    start, losses = 0, []
    if ckpt is not None:
        latest = ckpt.latest_restorable_step()
        if latest is not None:
            # config-compat peek BEFORE the tree restore: a grad_compress
            # mismatch changes the leaf count, and a dtype_policy mismatch
            # must fail loudly, not fork the loss curve silently
            _check_resume_policy(ckpt.manifest_extra(latest), cfg)
            restored, extra = ckpt.restore(latest, state_tree(g, opt, err))
            if compress == "int8":
                g, opt, err = restored
            else:
                g, opt = restored
            if sched is not None and extra.get("schedule"):
                sched.load_state(extra["schedule"])
            if ex is not None and extra.get("exchange"):
                ex.load_state(extra["exchange"])
            start = latest
    if start == 0 and warm_start is not None:
        # warm start = an IN-MEMORY resume: the timeseries driver hands us
        # the previous timestep's merged state + schedule extras, and we
        # take the exact resume path (restored caps/budgets, no init
        # re-probe, densify-key fast-forward below).  An on-disk checkpoint
        # for THIS run wins — it is strictly newer than the warm seed.
        wtree, wextra, wstep = warm_start
        wextra = wextra or {}
        _check_resume_policy(wextra, cfg)
        g, opt = wtree[0], wtree[1]
        # err stays zeros: the int8 error-feedback residual never crosses
        # a timestep boundary (same reset contract as densify/rebalance —
        # the new timestep's field moved under the rows)
        if sched is not None and wextra.get("schedule"):
            sched.load_state(wextra["schedule"])
        if ex is not None and wextra.get("exchange"):
            ex.load_state(wextra["exchange"])
        start = wstep
    # fast-forward the densify key stream consumed before ``start`` so a
    # resumed run splits the same keys as an uninterrupted one
    for i in range(start):
        if densify_every and i >= densify_from \
                and (i + 1) % densify_every == 0:
            key = jax.random.split(key, 1 + Pn)[0]

    g_dev = _put_unaliased(g, g_sh)
    opt_dev = jax.device_put(opt, opt_sh)
    err_dev = jax.device_put(err, err_sh) if compress == "int8" else None

    # tile-assignment resolution — the same render.resolve_assignment
    # policy as fit_partition (probe the WHOLE rig's concrete bbox counts
    # for a static sorted budget, or demote "auto" to dense for big-splat
    # scenes), so both drivers land on identical (impl, budget) for the
    # same scene; the probe is a jitted GLOBAL max, identical on every
    # host.  Re-resolved after every densify (radii train).
    assign = {"impl": cfg.assign_impl, "budget": cfg.assign_budget}

    def probe_assign(gg):
        impl, budget = resolve_assignment(gg, cams, grid,
                                          assign_impl=cfg.assign_impl,
                                          assign_budget=cfg.assign_budget)
        assign.update(impl=impl, budget=budget)

    # probe minibatches, shared by the tier probe and the exchange-budget
    # probe: the first one — and, mirroring fit_partition's
    # min(n_views, max(vb, 2))-view probe, a SECOND minibatch when vb == 1
    # (a single-view probe would size caps/budgets from one view only);
    # both probes max-merge the telemetry so the static shapes cover the
    # worst probed minibatch of the step's exact folded domain
    n_probe = 2 if vb < 2 and V > 1 else 1
    if cfg.exchange:
        # per-edge budgets have no worst-edge slack to hide behind: an
        # unprobed view whose overlap pattern differs can starve a single
        # edge.  Probe a few more minibatches (still bounded) — the
        # overflow counter + in-step demand remain the safety net.
        n_probe = max(n_probe, min(-(-V // vb), 4))
    probe_cams = [
        jax.device_put(
            select(cams, jnp.asarray((b * vb + np.arange(vb)) % V)),
            b_sh["cam"])
        for b in range(n_probe)]

    reprobe = None
    if sched is not None:
        def reprobe(gg):
            probe_gs_schedule(sched, mesh, grid, gg, probe_cams, views=vb,
                              assign_impl=assign["impl"],
                              assign_budget=assign["budget"],
                              exchange=cfg.exchange)

    def reprobe_exchange(gg):
        # pinned budgets (explicit cfg.exchange_budget / checkpoint-restored
        # state) are never re-probed — resume keeps its grown budget
        if ex is not None and not ex_pinned:
            probe_gs_exchange(ex, mesh, grid, gg, probe_cams, views=vb,
                              per_edge=ex_per_edge)

    probe_assign(g_dev)
    if sched is not None and sched.tier_caps is None:
        # a resume restored caps: no re-probe
        reprobe(g_dev)
    if ex is not None and ex.budget is None:
        # a resume restored the budget: no re-probe
        probe_gs_exchange(ex, mesh, grid, g_dev, probe_cams, views=vb,
                          per_edge=ex_per_edge)

    opt_vax = GSOptState(m=0, v=0, step=None, grad_accum=0, grad_count=0)
    dcfg = dataclasses.replace(cfg, densify_cap=densify_cap) \
        if densify_cap is not None else cfg
    densify = jax.jit(jax.vmap(
        partial(densify_and_prune, cfg=dcfg, extent=extent),
        in_axes=(0, opt_vax, 0), out_axes=(0, opt_vax)))

    step_cache = {}
    ex_demand = None        # running max of the step's in-step demand probe

    def get_step():
        spec = ((sched.k_tiers, sched.tier_caps) if sched else None,
                assign["impl"], assign["budget"],
                cfg.exchange, ex.budget_key() if ex else None)
        if spec not in step_cache:
            # the recompile marker: the next dispatch compiles this program
            caps = {f"cap_k{k}": c for k, c in zip(
                sched.k_tiers, sched.tier_caps or ())} if sched else {}
            with span("fit.build", **caps):
                step_cache[spec] = make_gs_train_step(
                    mesh, cfg, grid, extent, impl=impl, views=vb,
                    k_tiers=sched.k_tiers if sched else None,
                    tier_caps=sched.tier_caps if sched else None,
                    return_overflow=True, win_size=win_size,
                    assign_impl=assign["impl"],
                    assign_budget=assign["budget"],
                    exchange=cfg.exchange,
                    exchange_budget=ex.budget if ex else None)
        return step_cache[spec]

    def save(step_no):
        tree = jax.tree.map(jax.device_get,
                            state_tree(g_dev, opt_dev, err_dev))
        ckpt.save(step_no, tree,
                  extra={"schedule": sched.state_dict() if sched else None,
                         "exchange": ex.state_dict() if ex else None,
                         "dtype_policy": cfg.dtype_policy,
                         "grad_compress": cfg.grad_compress})

    def reset_err():
        # re-layout events (densify grow/prune, rebalance permutation)
        # invalidate the per-row int8 residuals: rows moved or changed
        # count, so the carried error no longer aligns.  Dropping it is
        # bounded (one quantisation step of error, at rare events) and
        # honest — stale residuals would inject noise into the WRONG rows.
        nonlocal err_dev
        if compress == "int8":
            err_dev = jax.device_put(
                jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32),
                             g_dev.trainable()), err_sh)

    for i in range(start, steps):
        with step_span(i):
            with span("fit.put"):
                vi = (i * vb + np.arange(vb)) % V
                batch = {
                    "gt_tiles": jax.device_put(jnp.asarray(gt_tiles[vi]),
                                               b_sh["gt_tiles"]),
                    "mask_tiles": jax.device_put(jnp.asarray(mask_tiles[vi]),
                                                 b_sh["mask_tiles"]),
                    "cam": jax.device_put(select(cams, jnp.asarray(vi)),
                                          b_sh["cam"]),
                }
            step_fn = get_step()
            with span("fit.dispatch"):
                if compress == "none":
                    out = step_fn(g_dev, opt_dev, batch)
                    g_dev, opt_dev, loss = out[:3]
                    ov = out[3]
                else:
                    out = step_fn(g_dev, opt_dev, err_dev, batch)
                    g_dev, opt_dev, err_dev, loss = out[:4]
                    ov = out[4]
            with span("fit.sync"):
                losses.append(float(loss))
            with span("fit.schedule"):
                if sched is not None:
                    # a non-zero (psum'd) counter grows the caps for the
                    # NEXT steps — a one-step blip, never a persistent
                    # silent truncation
                    sched.note_overflow(ov["tiles"], m_dev)
                if assign["impl"] == "sorted" \
                        and int(np.asarray(ov["assign"]).sum()) > 0:
                    # radii drifted past the sorted budget's probe slack
                    # between densify events: grow it geometrically (same
                    # honesty contract)
                    assign["budget"] = grow_tile_budget(
                        assign["budget"] or DEFAULT_TILE_BUDGET,
                        grid.n_tiles)
                if ex is not None:
                    # matrix budgets grow only the starved edges (per-edge
                    # psum'd counter); scalar budgets keep the total-count
                    # contract
                    ex.note_overflow(ov.get("exchange_edges",
                                            ov["exchange"]), Nl)
                    if "exchange_demand" in ov:
                        dm = np.asarray(ov["exchange_demand"])
                        ex_demand = dm if ex_demand is None \
                            else np.maximum(ex_demand, dm)
            if densify_every and i >= densify_from \
                    and (i + 1) % densify_every == 0:
                with span("fit.densify"):
                    ks = jax.random.split(key, 1 + Pn)
                    key = ks[0]
                    g_dev, opt_dev = densify(g_dev, opt_dev, ks[1:])
                    # the vmapped densify jit picks its own output
                    # shardings; pin the state back onto the step's
                    # (pod, part) layout before the next donating pjit call
                    g_dev = jax.device_put(g_dev, g_sh)
                    opt_dev = jax.device_put(opt_dev, opt_sh)
                    reset_err()  # row count changed: residuals misaligned
                    probe_assign(g_dev)  # splat sizes shifted: re-size
                    if sched is not None:
                        reprobe(g_dev)  # occupancy shifted: re-pick tiers
                    if ex is not None and not ex_pinned \
                            and ex_demand is not None:
                        # in-step resize, no host probe round-trip:
                        # densify clones at most cfg.max_new rows per
                        # partition, so the running per-edge demand +
                        # max_new upper-bounds the post-densify overlap
                        ex.ensure(ex_demand + cfg.max_new, Nl)
                    else:
                        reprobe_exchange(g_dev)  # overlap shifted too
            if rebalance_every and (i + 1) % rebalance_every == 0:
                with span("fit.densify"):
                    g_reb, opt_reb, moved = rebalance_partitions(
                        g_dev, opt_dev, mesh, threshold=rebalance_threshold)
                    if moved:
                        g_dev = jax.device_put(g_reb, g_sh)
                        opt_dev = jax.device_put(opt_reb, opt_sh)
                        reset_err()  # rows permuted across shards
                        # rows moved to different shards: the demand
                        # history no longer describes any edge — drop it
                        # and host-probe once
                        ex_demand = None
                        reprobe_exchange(g_dev)
            if ckpt is not None and ckpt_every \
                    and (i + 1) % ckpt_every == 0 and (i + 1) < steps:
                with span("fit.ckpt"):
                    save(i + 1)
        if log_every and (i + 1) % log_every == 0:
            print(f"  step {i+1:5d}  loss {losses[-1]:.4f}  "
                  f"schedule {sched if sched else 'dense'}")
    if ckpt is not None and steps > start:
        with span("fit.ckpt"):
            save(steps)
    return g_dev, opt_dev, losses

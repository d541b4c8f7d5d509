"""GS render serving: a batched request-queue server over a merged model.

The training side of the paper ends at "merge splats for global rendering";
this module is the read path that makes the merged model answer camera
requests at production rates (ROADMAP north star).  One server holds ONE
merged gaussian set and turns a stream of camera requests into batched
renders:

  submit(cam) -> bounded queue -> flush() coalesces pending requests into
  view-batched dispatches (the V axis of render_batch is the batching
  axis) -> per-request RenderResult, in submission order.

Three serving mechanisms ride on the batcher:

  pose-bucket assignment cache
      Each request's pose is snapped to a quantized bucket
      (``tiling.quantize_pose``) and the per-view (T, K) assignment table
      is cached host-side under that bucket key.  A hit skips
      ``assign_tiles`` entirely — the render becomes project -> gather ->
      rasterize from the cached table (``render.render_batch_tables``)
      and is BIT-IDENTICAL to the cold miss that populated the entry
      (both render the canonical bucket pose through the same program).
      LRU eviction under a static entry budget; evictions and inserts
      dropped by a zero budget are counted, never silent.

  LOD ladder
      Opacity/scale-pruned variants of the merged model, built once at
      load time by ranking live splats by screen impact (dedupe_mask-style
      boolean compaction; the smallest rung optionally capped
      GeoGaussian-style).  Requests select a rung by camera distance —
      deterministic and monotone (``select_rung``).

  load shedding
      Under queue pressure (pending >= shed_at) requests are still served
      — never dropped — but at a lower rung of the serving K-ladder
      (``TierSchedule`` owns the ladder; the shed render slices the cached
      Kmax table down to the shed K via ``tiling.slice_table``).  Shed
      requests and over-cap rejections are counted.

Telemetry follows the honesty contract: every budget that can drop or
degrade work has a counter (``hits/misses/evictions/cache_overflow/
shed/rejected`` plus the assignment-budget overflow key ``assign``), and a
zero counter is the machine-checked statement that nothing was dropped.
Contract suite: tests/test_serving.py; CLI: launch/serve_gs.py.

Tracing (``core.trace``): ``submit`` and ``flush`` run under the host
spans ``gs.serve.submit`` / ``gs.serve.flush``; each dispatch under
``gs.serve.dispatch``, which holds ``gs.serve.assign`` (the miss call, up
to the tables on the host), ``gs.serve.stage`` (stack, slice and upload
the tables), ``gs.serve.render`` and ``gs.serve.fetch`` (image readback).
"""

from __future__ import annotations

import dataclasses
import os
from collections import OrderedDict
from typing import Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.cameras import Camera, select
from repro.core.gaussians import Gaussians
from repro.core.render import assign_tables_jit, render_tables_jit
from repro.core.tiling import (DEFAULT_ASSIGN_IMPL, POSE_BINS, TierSchedule,
                               TileGrid, grow_tile_budget, quantize_pose,
                               slice_table)
from repro.core.trace import span


class QueueFullError(RuntimeError):
    """Raised by ``submit`` when the bounded queue is at capacity; the
    rejection is counted in telemetry["rejected"] before raising (the
    never-silent half of the shedding contract)."""


# ---------------------------------------------------------------------------
# LOD ladder: impact-ranked pruning masks + compaction
# ---------------------------------------------------------------------------


def splat_impact(g: Gaussians) -> np.ndarray:
    """(N,) float64 screen-impact score for LOD ranking: opacity x mean
    squared scale (~ the splat's expected pixel footprint x its alpha).
    Inactive rows score -inf so they can never outrank a live splat."""
    active = np.asarray(g.active)
    alpha = 1.0 / (1.0 + np.exp(-np.asarray(g.opacity_logit, np.float64)))
    area = np.exp(2.0 * np.asarray(g.log_scales, np.float64)).mean(-1)
    return np.where(active, alpha * area, -np.inf)


def lod_keep_mask(g: Gaussians, frac: float,
                  cap: Optional[int] = None) -> np.ndarray:
    """(N,) bool keep mask: the top ``ceil(frac * n_live)`` live splats by
    ``splat_impact`` (optionally capped at ``cap`` rows — the
    GeoGaussian-style floor for the smallest rung).  Deterministic: stable
    argsort, ties broken by row index; frac=1.0 keeps every live row."""
    active = np.asarray(g.active)
    n_live = int(active.sum())
    n_keep = min(n_live, int(np.ceil(float(frac) * n_live)))
    if cap is not None:
        n_keep = min(n_keep, int(cap))
    order = np.argsort(-splat_impact(g), kind="stable")
    keep = np.zeros(active.shape[0], bool)
    keep[order[:n_keep]] = True
    return keep & active


def compact(g: Gaussians, keep: np.ndarray, *,
            round_to: int = 256) -> Gaussians:
    """dedupe_mask-style boolean compaction of ``keep`` rows into a fresh
    buffer whose capacity rounds up to ``round_to`` (pad rows inactive) so
    nearby rung sizes share jit traces.  Row order is preserved."""
    n = int(np.asarray(keep).sum())
    cap = max(round_to, -(-n // round_to) * round_to)
    fields = {}
    for name in Gaussians._fields:
        a = np.asarray(getattr(g, name))[np.asarray(keep)]
        pad = ((0, cap - n),) + ((0, 0),) * (a.ndim - 1)
        fields[name] = jnp.asarray(np.pad(a, pad))   # bool pad -> False
    return Gaussians(**fields)


def build_lod_ladder(g: Gaussians, fracs: Sequence[float], *,
                     cap: Optional[int] = None,
                     round_to: int = 256) -> List[Gaussians]:
    """One compacted model per rung: rung 0 keeps ``fracs[0]`` (normally
    1.0 — the full merged model), later rungs keep less; only the LAST
    (coarsest) rung is additionally capped at ``cap`` rows."""
    rungs = []
    for i, frac in enumerate(fracs):
        rung_cap = cap if i == len(fracs) - 1 else None
        rungs.append(compact(g, lod_keep_mask(g, frac, rung_cap),
                             round_to=round_to))
    return rungs


def camera_eye(view) -> np.ndarray:
    """(4,4) world->camera matrix -> (3,) world-space camera position
    (view = [R | t] with t = -R @ eye, so eye = -R.T @ t)."""
    v = np.asarray(view, np.float64)
    return -v[:3, :3].T @ v[:3, 3]


def camera_distance(view, center) -> float:
    """Distance from the camera eye to the scene center — the LOD
    selection coordinate."""
    return float(np.linalg.norm(camera_eye(view)
                                - np.asarray(center, np.float64)))


def select_rung(distance: float, thresholds: Sequence[float]) -> int:
    """LOD rung for a camera distance: the number of ladder thresholds the
    camera sits beyond.  Deterministic and monotone non-decreasing in
    ``distance`` by construction (thresholds must be ascending)."""
    rung = 0
    for t in thresholds:
        if distance > float(t):
            rung += 1
    return rung


# ---------------------------------------------------------------------------
# Server
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ServeCfg:
    """Static serving configuration (hashable: jit cache keys derive from
    its fields).  The ladder/caching/shedding knobs all follow the honesty
    contract — each one's effect is visible in the telemetry dict."""
    K: int = 64                       # assignment depth (cached table K)
    k_ladder: Tuple[int, ...] = ()    # serving K ladder; () = auto from K
    impl: str = "auto"
    bg: float = 1.0
    max_batch: int = 8                # views per coalesced dispatch
    queue_cap: int = 64               # bounded queue capacity
    shed_at: Optional[int] = None     # pending depth that starts shedding
                                      # (default: queue_cap // 2)
    shed_rung: int = 0                # ladder rung served under pressure
    cache_entries: int = 64           # pose-bucket cache LRU budget
    pose_bins: float = POSE_BINS      # quantization (buckets per unit)
    lod_fracs: Tuple[float, ...] = (1.0, 0.4)   # keep-fraction per rung
    lod_cap: Optional[int] = None     # cap on the coarsest rung's rows
    lod_dists: Tuple[float, ...] = ()  # rung thresholds; () = auto
    lod_round_to: int = 256
    assign_impl: str = DEFAULT_ASSIGN_IMPL
    assign_budget: Optional[int] = None
    dtype_policy: str = "f32"         # "bf16" halves the cached (T, K)
                                      # tables; compositing stays f32
                                      # (core.dtypes contract)

    def __post_init__(self):
        from repro.core.dtypes import check_policy
        check_policy(self.dtype_policy)

    def resolved_ladder(self) -> Tuple[int, ...]:
        """Serving K ladder, ascending, topped by ``K`` (the GSTrainCfg
        "auto" tier idiom): shed renders pick a lower rung, full-quality
        renders use the top."""
        if self.k_ladder:
            ks = tuple(int(k) for k in self.k_ladder)
            if ks != tuple(sorted(ks)) or ks[-1] != self.K:
                raise ValueError(f"k_ladder must ascend to K={self.K}: {ks}")
            return ks
        return tuple(sorted({max(1, self.K // 8), max(1, self.K // 2),
                             self.K}))


@dataclasses.dataclass
class RenderResult:
    """One served request: images + the serving decisions that shaped them
    (rung/K/hit/shed are the observable halves of the LOD, cache and
    shedding contracts the suite pins)."""
    request_id: int
    rgb: np.ndarray          # (H, W, 3)
    coverage: np.ndarray     # (H, W)
    rung: int                # LOD rung served
    K: int                   # per-tile depth rendered (< ladder top == shed)
    cache_hit: bool
    shed: bool


@dataclasses.dataclass
class _Request:
    rid: int
    cam: Camera              # canonical (bucket-snapped) single-view camera
    key: tuple               # pose bucket key
    rung: int
    k: int
    shed: bool
    hit: bool


def _pad_pow2(n: int, cap: int) -> int:
    """Next power-of-two batch size <= cap: bounded trace count per config
    (log2(max_batch)+1) without render_views' fixed full-batch padding —
    a lone request must not pay an 8-view dispatch."""
    p = 1
    while p < n:
        p *= 2
    return min(p, cap)


class GSRenderServer:
    """One merged model, served.  Synchronous core (submit/flush), so tests
    and CI drive it deterministically; a transport layer would own threads.

    ``g`` is the merged model (``merge.merge_partitions`` output or a
    restored merged checkpoint — see ``from_checkpoint``); ``center`` /
    ``radius`` anchor the LOD distance ladder (probed from the live means
    when omitted)."""

    def __init__(self, g: Gaussians, grid: TileGrid,
                 cfg: Optional[ServeCfg] = None, *, center=None,
                 radius: Optional[float] = None):
        self.cfg = cfg = cfg or ServeCfg()
        self.grid = grid
        # TierSchedule owns the serving K ladder (the same cap machinery
        # the trainer grows); shedding serves schedule.k_tiers[shed_rung],
        # full quality serves schedule.kmax == cfg.K.
        self.schedule = TierSchedule(cfg.resolved_ladder())
        if not (0 <= cfg.shed_rung < len(self.schedule.k_tiers)):
            raise ValueError(f"shed_rung {cfg.shed_rung} outside ladder "
                             f"{self.schedule.k_tiers}")

        live = np.asarray(g.active)
        means = np.asarray(g.means, np.float64)[live]
        if center is None:
            center = 0.5 * (means.max(0) + means.min(0)) if len(means) \
                else np.zeros(3)
        self.center = np.asarray(center, np.float64)
        if radius is None:
            radius = float(np.linalg.norm(means - self.center, axis=-1).max()) \
                if len(means) else 1.0
        self.radius = float(radius)

        self.ladder = build_lod_ladder(g, cfg.lod_fracs, cap=cfg.lod_cap,
                                       round_to=cfg.lod_round_to)
        n_thresh = len(cfg.lod_fracs) - 1
        if cfg.lod_dists:
            if len(cfg.lod_dists) != n_thresh:
                raise ValueError(
                    f"lod_dists needs {n_thresh} thresholds for "
                    f"{len(cfg.lod_fracs)} rungs, got {len(cfg.lod_dists)}")
            self.lod_dists = tuple(float(d) for d in cfg.lod_dists)
        else:
            # auto ladder: rung i+1 beyond ~4x the scene radius, doubling
            # per rung — orbit-distance cameras stay on the full model
            self.lod_dists = tuple(self.radius * 4.0 * (2.0 ** i)
                                   for i in range(n_thresh))

        # per-rung assignment impl/budget, re-resolved on assign overflow
        # (grow_tile_budget) so a starved budget is counted AND repaired
        self._assign: List[Tuple[str, Optional[int]]] = [
            (cfg.assign_impl, cfg.assign_budget) for _ in self.ladder]
        self._cache: "OrderedDict[tuple, Tuple[np.ndarray, np.ndarray]]" = \
            OrderedDict()
        self._queue: List[_Request] = []
        self._next_rid = 0
        self._telemetry: Dict[str, int] = {
            "requests": 0, "batches": 0, "hits": 0, "misses": 0,
            "evictions": 0, "cache_overflow": 0, "shed": 0, "rejected": 0,
            "assign": 0,
        }

    # -- checkpoint loading -------------------------------------------------

    #: subdirectory of a ``launch/train.py --gs`` checkpoint tree holding
    #: the merged-model checkpoint (written after merge, alongside the
    #: per-partition ``partitions/`` tree)
    MERGED_SUBDIR = "merged"

    @classmethod
    def from_checkpoint(cls, ckpt_dir: str,
                        cfg: Optional[ServeCfg] = None, **overrides):
        """Load the merged checkpoint a ``launch/train.py --gs`` run wrote
        under ``<ckpt_dir>/merged`` and build a server around it ->
        ``(server, extra)``.  The template is shape-free
        (``checkpoint.unshaped_like``): the merged capacity is a training
        outcome the serving process cannot know ahead of the restore.
        ``extra["scene"]`` (center/radius/resolution/tile shape) anchors
        the grid and the LOD ladder; cfg.K defaults to the training K.
        ``overrides`` are ServeCfg field replacements applied over the
        meta-defaulted cfg (CLI idiom; mutually exclusive with ``cfg``)."""
        from repro.runtime.checkpoint import (CheckpointManager,
                                              dequantize_cold, unshaped_like)
        if cfg is not None and overrides:
            raise ValueError("pass cfg= or field overrides, not both")
        mgr = CheckpointManager(os.path.join(ckpt_dir, cls.MERGED_SUBDIR),
                                keep=2)
        g, extra, step = mgr.restore_latest(unshaped_like(Gaussians))
        # int8 cold-attribute checkpoints (launch/train.py --ckpt-quantize)
        # ride their per-tensor scales on extra["quant"]; no-op otherwise
        if step is not None:
            g = dequantize_cold(g, extra.get("quant"))
        if step is None:
            raise FileNotFoundError(
                f"no merged checkpoint under {ckpt_dir}/{cls.MERGED_SUBDIR} "
                "(run launch/train.py --gs first)")
        meta = extra.get("scene", {})
        res = int(meta.get("resolution", 64))
        grid = TileGrid(res, res, int(meta.get("tile_h", 8)),
                        int(meta.get("tile_w", 16)))
        if cfg is None:
            cfg = dataclasses.replace(
                ServeCfg(K=int(meta.get("K", ServeCfg.K))), **overrides)
        center = meta.get("center")
        radius = meta.get("radius")
        server = cls(g, grid, cfg,
                     center=None if center is None else np.asarray(center),
                     radius=None if radius is None else float(radius))
        return server, extra

    # -- request intake -----------------------------------------------------

    @property
    def pending(self) -> int:
        return len(self._queue)

    def telemetry(self) -> Dict[str, int]:
        """Copy of the serving counters.  Honesty contract: ``shed`` /
        ``rejected`` / ``evictions`` / ``cache_overflow`` count every
        degraded or refused unit of work; ``assign`` counts assignment
        candidates dropped past a sorted-path budget (0 == every table
        exact).  The render from a table drops nothing: it has no tier
        caps."""
        return dict(self._telemetry)

    def clear_cache(self):
        """Drop every cached table (bench/test hook for re-measuring the
        cold path); telemetry counters are NOT reset."""
        self._cache.clear()

    def cached_table(self, cam: Camera, *, rung: int = 0):
        """The cached (idx, score) table a request for ``cam`` at ``rung``
        would hit, or None — test/introspection hook (does not touch LRU
        order or counters)."""
        key, _ = quantize_pose(cam.view, cam.fx, cam.fy,
                               bins=self.cfg.pose_bins)
        return self._cache.get((key, rung))

    def submit(self, cam: Camera) -> int:
        """Enqueue one camera request -> request id (dense from 0, the
        order ``flush`` results preserve).  Raises QueueFullError at the
        queue cap (counted).  Past ``shed_at`` pending requests the
        request is marked shed: still served, at the ladder's
        ``shed_rung`` K (counted, never dropped)."""
        with span("serve.submit", rid=self._next_rid):
            if np.asarray(cam.view).shape != (4, 4):
                raise ValueError("submit takes a single-view Camera; use "
                                 "serve() for a batched rig")
            if (cam.width, cam.height) != (self.grid.width, self.grid.height):
                raise ValueError(
                    f"camera {cam.width}x{cam.height} does not match the "
                    f"serving grid {self.grid.width}x{self.grid.height}")
            cfg = self.cfg
            if len(self._queue) >= cfg.queue_cap:
                self._telemetry["rejected"] += 1
                raise QueueFullError(
                    f"request queue at cap {cfg.queue_cap}; rejection counted "
                    "(telemetry['rejected'])")
            shed_at = cfg.shed_at if cfg.shed_at is not None \
                else max(1, cfg.queue_cap // 2)
            shed = len(self._queue) >= shed_at
            key, (cview, cfx, cfy) = quantize_pose(
                cam.view, cam.fx, cam.fy, bins=cfg.pose_bins)
            canon = Camera(jnp.asarray(cview), jnp.float32(cfx), jnp.float32(cfy),
                           cam.width, cam.height)
            rung = select_rung(camera_distance(cview, self.center),
                               self.lod_dists)
            k = int(self.schedule.k_tiers[cfg.shed_rung]) if shed \
                else int(self.schedule.kmax)
            rid = self._next_rid
            self._next_rid += 1
            self._telemetry["requests"] += 1
            if shed:
                self._telemetry["shed"] += 1
            self._queue.append(_Request(rid=rid, cam=canon, key=key, rung=rung,
                                        k=k, shed=shed, hit=False))
            return rid

    # -- cache --------------------------------------------------------------

    def _cache_get(self, key: tuple, rung: int):
        entry = self._cache.get((key, rung))
        if entry is not None:
            self._cache.move_to_end((key, rung))
            self._telemetry["hits"] += 1
        else:
            self._telemetry["misses"] += 1
        return entry

    def _cache_put(self, key: tuple, rung: int, idx: np.ndarray,
                   score: np.ndarray):
        if self.cfg.cache_entries <= 0:
            # zero budget: nothing can be cached — counted, not silent
            self._telemetry["cache_overflow"] += 1
            return
        self._cache[(key, rung)] = (idx, score)
        self._cache.move_to_end((key, rung))
        while len(self._cache) > self.cfg.cache_entries:
            self._cache.popitem(last=False)
            self._telemetry["evictions"] += 1

    # -- batching -----------------------------------------------------------

    def _stack_cams(self, reqs: List[_Request], pad_to: int) -> Camera:
        take = reqs + [reqs[-1]] * (pad_to - len(reqs))
        return Camera(view=jnp.stack([r.cam.view for r in take]),
                      fx=jnp.stack([r.cam.fx for r in take]),
                      fy=jnp.stack([r.cam.fy for r in take]),
                      width=self.grid.width, height=self.grid.height)

    def _tables_for(self, reqs: List[_Request], rung: int):
        """Per-request (T, Kmax) tables: cache hits read host-side, misses
        batch through assign_tables_jit and populate the cache."""
        cfg = self.cfg
        tables: Dict[int, Tuple[np.ndarray, np.ndarray]] = {}
        misses = []
        for i, r in enumerate(reqs):
            entry = self._cache_get(r.key, rung)
            if entry is None:
                misses.append(i)
            else:
                r.hit = True
                tables[i] = entry
        if misses:
            impl, budget = self._assign[rung]
            pad = _pad_pow2(len(misses), cfg.max_batch)
            miss_reqs = [reqs[i] for i in misses]
            with span("serve.assign", n=len(misses), pad=pad):
                cams = self._stack_cams(miss_reqs, pad)
                idx, score, ov = assign_tables_jit(
                    self.grid, cfg.K, None, impl, budget)(self.ladder[rung],
                                                          cams)
                idx, score = np.asarray(idx), np.asarray(score)
                n_ov = int(np.asarray(ov)[: len(misses)].sum())
            if n_ov:
                # starved sorted-path budget: count it and grow for future
                # misses (already-cached tables stay as extracted — their
                # drops were counted when they happened)
                self._telemetry["assign"] += n_ov
                if budget is not None:
                    self._assign[rung] = (
                        impl, grow_tile_budget(budget, self.grid.n_tiles))
            for j, i in enumerate(misses):
                entry = (idx[j], score[j])
                tables[i] = entry
                self._cache_put(reqs[i].key, rung, *entry)
        return [tables[i] for i in range(len(reqs))]

    def _dispatch(self, reqs: List[_Request]) -> List[RenderResult]:
        """Render one (rung, k)-homogeneous group of <= max_batch requests
        as a single view-batched dispatch from assignment tables."""
        cfg = self.cfg
        rung, k = reqs[0].rung, reqs[0].k
        pad = _pad_pow2(len(reqs), cfg.max_batch)
        with span("serve.dispatch", rung=rung, k=k, n=len(reqs), pad=pad,
                  rid=reqs[0].rid):
            tables = self._tables_for(reqs, rung)
            with span("serve.stage"):
                take = tables + [tables[-1]] * (pad - len(reqs))
                idx = np.stack([t[0] for t in take])
                score = np.stack([t[1] for t in take])
                idx, score = slice_table(idx, score, k)   # shed: prefix
                cams = self._stack_cams(reqs, pad)
                idx, score = jnp.asarray(idx), jnp.asarray(score)
            with span("serve.render"):
                out = render_tables_jit(self.grid, cfg.impl, cfg.bg,
                                        dtype_policy=cfg.dtype_policy)(
                    self.ladder[rung], cams, idx, score)
            self._telemetry["batches"] += 1
            with span("serve.fetch"):
                rgb = np.asarray(out.rgb)
                cov = np.asarray(out.coverage)
        return [RenderResult(request_id=r.rid, rgb=rgb[i], coverage=cov[i],
                             rung=rung, K=k, cache_hit=r.hit, shed=r.shed)
                for i, r in enumerate(reqs)]

    def flush(self) -> List[RenderResult]:
        """Serve EVERY pending request -> results in submission order.

        Requests group by (rung, k) — one model and one table depth per
        dispatch — and each group coalesces into view-batched renders of
        up to ``max_batch`` views (padded to the next power of two, so
        each config compiles a bounded trace set)."""
        reqs, self._queue = self._queue, []
        results: List[RenderResult] = []
        with span("serve.flush", n=len(reqs)):
            groups: Dict[Tuple[int, int], List[_Request]] = {}
            for r in reqs:
                groups.setdefault((r.rung, r.k), []).append(r)
            for key in sorted(groups):
                rs = groups[key]
                for s in range(0, len(rs), self.cfg.max_batch):
                    results.extend(
                        self._dispatch(rs[s:s + self.cfg.max_batch]))
        return sorted(results, key=lambda r: r.request_id)

    def serve(self, rig: Camera) -> List[RenderResult]:
        """Convenience driver: submit every view of a batched rig and
        flush, in waves that respect the queue bound WITHOUT tripping the
        rejection counter (flush-before-full), -> results in rig order."""
        results = []
        for v in range(rig.view.shape[0]):
            if self.pending >= self.cfg.queue_cap:
                results.extend(self.flush())
            self.submit(select(rig, v))
        results.extend(self.flush())
        return sorted(results, key=lambda r: r.request_id)

"""The benchmark's own scene data: isosurface points, colors, partitions and
the orbital camera rig, made from the configuration and ``--seed`` alone.

Nothing here imports the program under test.  Each dataset's analytic
field is a file of its own, ``volumes/<dataset>.py``, found by name.  The
generator copies the program's data path (its volumes, the edge-crossing
extraction of ``repro.data.isosurface.point_cloud_for``, ``height_colors``,
``repro.core.cameras.orbital_rig`` and the median slab split with a ghost
halo of ``repro.core.partition``), so the cells train and serve the scene the
repository's users extract, while the reference and the program both take
their inputs from here.

What the seed does not change, the edge crossings of the analytic volume at
the configured grid resolution, is written once to ``.cache/`` beside this
file (about 50 MB) and read back by every later run of the checkout.  The
seed then picks the point subsample, so every seed yields exactly
``n_points`` points and every partition the configured capacity: the same
shapes, and so the same compiled programs, for every seed.
"""

from __future__ import annotations

import dataclasses
import os
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
CACHE = HERE / ".cache"


# ---------------------------------------------------------------------------
# Analytic volumes (seed-independent)
# ---------------------------------------------------------------------------


def _axis(res: int):
    return (np.arange(res, dtype=np.float32) + 0.5) / res


def volume(name: str, here: Path = HERE):
    """The field of dataset ``name``: ``field(x, y, z)`` of
    ``volumes/<name>.py``, whose isosurface is its zero set."""
    from harness import load_module
    path = here / "volumes" / f"{name}.py"
    if not path.exists():
        raise SystemExit(f"no volume {path} for dataset {name!r}")
    return load_module(path).field


def _field_slab(field, res: int, i0: int, i1: int):
    """Field values at x-slices [i0, i1) of the res^3 cell-centred grid."""
    a = _axis(res)
    x, y, z = np.meshgrid(a[i0:i1], a, a, indexing="ij")
    return field(x, y, z).astype(np.float32)


def edge_crossings(name: str, res: int, *, chunk: int = 32,
                   here: Path = HERE) -> np.ndarray:
    """Every grid edge along x, y or z where the field changes sign, as the
    linearly interpolated crossing point in [0, 1]^3 -> (M, 3) float32.

    Same points, in the same order, as the program's extraction: all x-edge
    crossings in C order of the (R-1, R, R) edge grid, then y, then z.
    Computed in x-slabs of ``chunk`` cells so the host never holds the whole
    field."""
    field = volume(name, here)
    per_axis = [[], [], []]
    for i0 in range(0, res, chunk):
        i1 = min(res, i0 + chunk + 1)          # one extra slice for x-edges
        f = _field_slab(field, res, i0, i1)
        own = min(chunk, res - i0)             # slices this slab owns
        for ax in range(3):
            if ax == 0:
                a, b = f[:-1], f[1:]
                if i1 - i0 <= own:             # last slab: no next slice
                    a, b = a[:own - 1], b[:own - 1]
                else:
                    a, b = a[:own], b[:own]
            else:
                sl0 = [slice(0, own), slice(None), slice(None)]
                sl1 = [slice(0, own), slice(None), slice(None)]
                sl0[ax] = slice(0, res - 1)
                sl1[ax] = slice(1, res)
                a, b = f[tuple(sl0)], f[tuple(sl1)]
            cross = (a * b) < 0
            t = a / (a - b + np.float32(1e-30))
            idx = np.argwhere(cross).astype(np.float32)
            idx[:, 0] += i0
            step = np.zeros((1, 3), np.float32)
            step[0, ax] = 1.0
            per_axis[ax].append((idx + t[cross][:, None] * step + 0.5) / res)
    return np.concatenate([np.concatenate(p) for p in per_axis]) \
        .astype(np.float32)


def cached_crossings(name: str, res: int, cache: Path = CACHE) -> np.ndarray:
    """``edge_crossings`` through the checkout's seed-independent cache."""
    path = cache / f"{name}_r{res}_crossings.npy"
    if path.exists():
        return np.load(path)
    pts = edge_crossings(name, res)
    cache.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(f".{os.getpid()}.tmp.npy")
    np.save(tmp, pts)
    os.replace(tmp, path)
    return pts


def height_colors(points: np.ndarray) -> np.ndarray:
    """Height + radial colormap in [0.05, 0.95] (the program's)."""
    z = points[:, 2]
    r = np.linalg.norm(points[:, :2] - 0.5, axis=1)
    c = np.stack([
        0.15 + 0.7 * z,
        0.2 + 0.6 * (1 - z) * (1 - np.clip(r * 1.4, 0, 1)),
        0.25 + 0.6 * np.clip(r * 1.4, 0, 1),
    ], axis=-1)
    return np.clip(c, 0.05, 0.95).astype(np.float32)


# ---------------------------------------------------------------------------
# Seeded scene
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Scene:
    points: np.ndarray          # (n, 3) float32
    colors: np.ndarray          # (n, 3) float32
    extent: float               # bounding-box diagonal
    center: np.ndarray          # (3,) float64
    rig_radius: float           # orbit radius of the training rig


def make_scene(cfg: dict, seed: int, *, cache: Path = CACHE) -> Scene:
    """The configuration's isosurface, subsampled by ``seed`` to exactly
    ``n_points`` points."""
    pts = cached_crossings(cfg["dataset"], int(cfg["grid_resolution"]),
                           cache)
    n = int(cfg["n_points"])
    if len(pts) < n:
        raise ValueError(f"{cfg['dataset']} at grid {cfg['grid_resolution']} "
                         f"has {len(pts)} crossings, fewer than n_points={n}")
    rng = np.random.default_rng(seed)
    sel = np.sort(rng.choice(len(pts), n, replace=False))
    points = pts[sel]
    lo, hi = points.min(0), points.max(0)
    extent = float(np.linalg.norm(hi - lo))
    return Scene(points=points, colors=height_colors(points), extent=extent,
                 center=0.5 * (hi.astype(np.float64) + lo),
                 rig_radius=1.6 * extent / 2 + 1e-3)


@dataclasses.dataclass
class Partition:
    points: np.ndarray          # (n_p, 3) owned rows, then ghost rows
    colors: np.ndarray
    owner: np.ndarray           # (n_p,) int32 source partition of each row


def partition(scene: Scene, n_parts: int, ghost_frac: float):
    """Equal-count slabs along x (median split for two), each with the
    points of its neighbours that lie within ``ghost_frac * extent`` of the
    cut appended as ghosts."""
    x = scene.points[:, 0]
    order = np.argsort(x, kind="stable")
    bounds = np.linspace(0, len(x), n_parts + 1).round().astype(int)
    part_of = np.empty(len(x), np.int32)
    for p in range(n_parts):
        part_of[order[bounds[p]:bounds[p + 1]]] = p
    cuts = [0.5 * (x[order[b - 1]] + x[order[b]]) for b in bounds[1:-1]]
    gw = ghost_frac * scene.extent
    parts = []
    for p in range(n_parts):
        own = np.nonzero(part_of == p)[0]
        near = np.zeros(len(x), bool)
        if p > 0:
            near |= (part_of == p - 1) & (x > cuts[p - 1] - gw)
        if p < n_parts - 1:
            near |= (part_of == p + 1) & (x < cuts[p] + gw)
        rows = np.concatenate([own, np.nonzero(near)[0]])
        parts.append(Partition(points=scene.points[rows],
                               colors=scene.colors[rows],
                               owner=part_of[rows].astype(np.int32)))
    return parts


# ---------------------------------------------------------------------------
# Cameras (world -> camera matrices; the camera looks down +z)
# ---------------------------------------------------------------------------


def look_at(eye, center, up=(0.0, 0.0, 1.0)) -> np.ndarray:
    eye = np.asarray(eye, np.float64)
    center = np.asarray(center, np.float64)
    up = np.asarray(up, np.float64)
    f = center - eye
    f = f / np.linalg.norm(f)
    s = np.cross(f, up)
    if np.linalg.norm(s) < 1e-8:
        s = np.cross(f, np.array([1.0, 0.0, 0.0]))
    s = s / np.linalg.norm(s)
    u = np.cross(s, f)
    m = np.eye(4)
    m[0, :3], m[1, :3], m[2, :3] = s, u, f
    m[0, 3] = -s @ eye
    m[1, 3] = -u @ eye
    m[2, 3] = -f @ eye
    return m


def focal(width: int, fov_deg: float = 50.0) -> float:
    return 0.5 * width / np.tan(np.radians(fov_deg) / 2)


def orbital_rig(n_views: int, center, radius: float) -> np.ndarray:
    """Fibonacci-spiral orbit -> (n_views, 4, 4) float32 view matrices."""
    center = np.asarray(center, np.float64)
    golden = (1 + 5 ** 0.5) / 2
    views = []
    for i in range(n_views):
        z = 0.95 * (2 * (i + 0.5) / n_views - 1)
        r = np.sqrt(max(1 - z * z, 1e-9))
        phi = 2 * np.pi * i / golden
        eye = center + radius * np.array([r * np.cos(phi), r * np.sin(phi),
                                          z])
        views.append(look_at(eye, center))
    return np.stack(views).astype(np.float32)


def eye_at(center, radius: float, azimuth: float, elevation: float):
    """World position on the sphere of ``radius`` about ``center``."""
    c = np.asarray(center, np.float64)
    return c + radius * np.array([np.cos(elevation) * np.cos(azimuth),
                                  np.cos(elevation) * np.sin(azimuth),
                                  np.sin(elevation)])


def pose(center, radius: float, azimuth: float, elevation: float):
    """(4, 4) float32 view of a camera on that sphere looking at
    ``center``."""
    return look_at(eye_at(center, radius, azimuth, elevation),
                   center).astype(np.float32)

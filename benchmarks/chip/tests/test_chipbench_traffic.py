"""Scene data, training schedule and pose traces: the same per seed,
different across seeds, and the same amount of work for every seed."""

import numpy as np
import pytest

import chipbench_tiny as tiny
import open_loop
import scene


@pytest.fixture(scope="module")
def cfg():
    return tiny.tiny_cfg("kingsnake-4m-512-train")


def test_scene_is_seeded_and_sized_alike(cfg, tmp_path):
    a = scene.make_scene(cfg, 2 ** 31 + 11, cache=tmp_path)
    b = scene.make_scene(cfg, 2 ** 31 + 11, cache=tmp_path)
    c = scene.make_scene(cfg, 5, cache=tmp_path)
    np.testing.assert_array_equal(a.points, b.points)
    assert not np.array_equal(a.points, c.points)
    assert len(a.points) == len(c.points) == cfg["n_points"]
    assert (tmp_path / "kingsnake_r40_crossings.npy").exists()


def test_crossings_match_the_programs_extraction(tmp_path):
    import repro.data.isosurface as iso
    iso._RES_CACHE[("kingsnake", 10 ** 9)] = 40
    want, _ = iso.point_cloud_for("kingsnake", 10 ** 9)
    np.testing.assert_array_equal(scene.edge_crossings("kingsnake", 40,
                                                       chunk=7), want)


def test_partitions_own_every_point_once_with_ghosts(cfg, tmp_path):
    sc = scene.make_scene(cfg, 3, cache=tmp_path)
    parts = scene.partition(sc, 2, cfg["ghost_frac"])
    owned = sum(int((p.owner == i).sum()) for i, p in enumerate(parts))
    assert owned == len(sc.points)
    assert all((p.owner != i).any() for i, p in enumerate(parts))
    assert max(len(p.points) for p in parts) <= cfg["capacity"]


@pytest.mark.parametrize("mix", ["orbit", "revisit"])
def test_pose_trace_is_seeded(mix):
    tr = tiny.tiny_traffic(mix)
    kw = dict(center=np.full(3, 0.5), rig_radius=1.4, seconds=20.0)
    a, pa = open_loop.make(tr, seed=2 ** 31 + 3, **kw)
    b, pb = open_loop.make(tr, seed=2 ** 31 + 3, **kw)
    c, _ = open_loop.make(tr, seed=99, **kw)
    rate = tr["arrivals"]["rate_rps"]
    assert len(a) == len(c) == round(rate * 20.0)
    assert [r.arrival_s for r in a] == [r.arrival_s for r in c]
    assert all(np.array_equal(x.view, y.view) for x, y in zip(a, b))
    assert not all(np.array_equal(x.view, y.view) for x, y in zip(a, c))
    gaps = np.diff([r.arrival_s for r in a])
    np.testing.assert_allclose(gaps, 1.0 / rate)
    assert any(r.far for r in a) and not all(r.far for r in a)
    if mix == "revisit":
        assert len(pa) == tr["poses"]["bookmarks"] and len(pb) == len(pa)
        distinct = {r.view.tobytes() for r in a}
        assert distinct <= {v.tobytes() for v, _ in pa}


def _repeats(reqs):
    seen, out = set(), []
    for r in reqs:
        out.append(r.view.tobytes() in seen)
        seen.add(r.view.tobytes())
    return out


@pytest.mark.parametrize("mix", ["orbit", "revisit"])
def test_every_seed_gets_the_same_shape_of_work(mix):
    """Rungs and repeats (cache hits) fall on the same requests for every
    seed; only the poses move."""
    tr = tiny.tiny_traffic(mix)
    kw = dict(center=np.full(3, 0.5), rig_radius=1.4, seconds=60.0)
    a, _ = open_loop.make(tr, seed=2 ** 31 + 5, **kw)
    c, _ = open_loop.make(tr, seed=17, **kw)
    assert [r.far for r in a] == [r.far for r in c]
    assert _repeats(a) == _repeats(c) and any(_repeats(a))


@pytest.mark.parametrize("kind,params", [
    ("paced", {"rate_rps": 2.0}),
    ("poisson", {"rate_rps": 2.0}),
    ("onoff", {"rate_rps": 2.0, "on_s": 5.0, "off_s": 15.0})])
def test_arrival_processes(kind, params):
    times = open_loop.kind("arrivals", kind).times
    a = times(params, 400.0, np.random.default_rng(1))
    b = times(params, 400.0, np.random.default_rng(1))
    np.testing.assert_array_equal(a, b)
    assert np.all(np.diff(a) >= 0) and 0 <= a[0] and a[-1] < 400.0
    assert abs(len(a) / 400.0 - 2.0) < 0.3
    if kind == "onoff":                        # nothing in an off period
        assert np.all(a % 20.0 < 5.0)


def test_orbit_sessions_step_and_dwell():
    tr = tiny.tiny_traffic("orbit")
    reqs, _ = open_loop.make(tr, center=np.full(3, 0.5), rig_radius=1.4,
                             seconds=60.0, seed=1)
    distinct = len({r.view.tobytes() for r in reqs})
    assert len(reqs) / 2 < distinct < len(reqs)      # dwells repeat poses

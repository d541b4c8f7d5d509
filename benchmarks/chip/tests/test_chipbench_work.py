"""The yardstick's overlap count and the reference's assignment against
brute force at a tiny size."""

import numpy as np
import pytest

import chipbench_tiny  # noqa: F401
import reference as ref
import work


def _splats(seed, n=300):
    import jax.numpy as jnp
    rng = np.random.default_rng(seed)
    return ref.Splats(
        mean=jnp.asarray(rng.uniform(-10, 74, (n, 2)), jnp.float32),
        cov=jnp.ones((n, 3), jnp.float32),
        depth=jnp.asarray(rng.choice(50, n) * 0.5 + 1, jnp.float32),
        rgb=jnp.ones((n, 3), jnp.float32) * 0.5,
        alpha=jnp.full((n,), 0.5, jnp.float32),
        radius=jnp.asarray(rng.integers(1, 30, n), jnp.float32),
        valid=jnp.asarray(rng.uniform(size=n) < 0.9))


def _brute_hits(sp, grid):
    m = np.asarray(sp.mean, np.float64)
    r = np.asarray(sp.radius, np.float64)
    hits = np.zeros((grid.n_tiles, len(r)), bool)
    for t in range(grid.n_tiles):
        lx = (t % grid.nx) * grid.tile_w
        ly = (t // grid.nx) * grid.tile_h
        dx = m[:, 0] - np.clip(m[:, 0], lx, lx + grid.tile_w)
        dy = m[:, 1] - np.clip(m[:, 1], ly, ly + grid.tile_h)
        hits[t] = (dx * dx + dy * dy <= r * r) & np.asarray(sp.valid)
    return hits


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_tile_counts_match_brute_force(seed):
    grid = ref.Grid(64, 48, 8, 16)
    sp = _splats(seed)
    slots = ref.slots_for(int(ref.needed_slots(sp, grid)))
    got = np.asarray(ref.tile_counts(sp, grid, slots))
    np.testing.assert_array_equal(got, _brute_hits(sp, grid).sum(1))


@pytest.mark.parametrize("seed", [0, 1])
def test_assignment_is_the_k_nearest_with_row_ties(seed):
    grid = ref.Grid(64, 48, 8, 16)
    sp = _splats(seed)
    K = 6
    slots = ref.slots_for(int(ref.needed_slots(sp, grid)))
    idx, live = ref.assign(sp, grid, K, slots)
    idx, live = np.asarray(idx), np.asarray(live)
    depth = np.asarray(sp.depth)
    for t, h in enumerate(_brute_hits(sp, grid)):
        rows = np.nonzero(h)[0]
        want = rows[np.lexsort((rows, depth[rows]))][:K]
        assert list(idx[t][live[t]]) == list(want)


def test_capped_counts_sum_min_n_k():
    import jax.numpy as jnp
    grid = ref.Grid(64, 48, 8, 16)
    rng = np.random.default_rng(3)
    n = 400
    tr = {"means": jnp.asarray(rng.uniform(0.3, 0.7, (n, 3)), jnp.float32),
          "log_scales": jnp.full((n, 3), np.log(0.02), jnp.float32),
          "quats": jnp.asarray(rng.standard_normal((n, 4)), jnp.float32),
          "opacity_logit": jnp.zeros((n,), jnp.float32),
          "colors": jnp.zeros((n, 3), jnp.float32)}
    import scene
    view = jnp.asarray(scene.look_at([0.5, -1.0, 0.6], [0.5, 0.5, 0.5]),
                       jnp.float32)
    active = jnp.ones((n,), bool)
    f = scene.focal(64)
    sp = ref.project(tr, active, view, f, grid)
    brute = _brute_hits(sp, grid).sum(1)
    assert work.CappedCounts(grid, 5)(tr, active, view, f) == \
        np.minimum(brute, 5).sum()


def test_roofline_names_its_bound_and_skips_no_time():
    peak = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.roofline_share(50.0, 1.0, 1.0, peak) == (50.0, "flops")
    assert work.roofline_share(1.0, 5.0, 1.0, peak) == (50.0, "bytes")
    assert work.roofline_share(1.0, 5.0, 0.0, peak) == (None, None)

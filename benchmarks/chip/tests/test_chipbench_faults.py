"""The rest of a run with the timed path broken underneath: ``correct``
must come out false for every fault the cells can have.  (The cells run on
one chip, so there is no exchange between chips to leave out.)"""

import jax
import jax.numpy as jnp
import pytest

import chipbench_tiny as tiny


@pytest.fixture(scope="module")
def cache(tmp_path_factory):
    return tmp_path_factory.mktemp("chipbench_fault_cache")


def test_step_returning_its_state_unchanged_is_caught(cache, monkeypatch):
    import repro.core.distributed as dist
    orig = dist.make_gs_train_step

    def make(*a, **k):
        fn = orig(*a, **k)

        def step(g, opt, batch):
            kept = jax.tree.map(jnp.copy, (g, opt))
            return kept + tuple(fn(g, opt, batch)[2:])
        return step
    monkeypatch.setattr(dist, "make_gs_train_step", make)
    out, _ = tiny.run_cell("kingsnake-4m-512-train", "steady", cache,
                           impl="ref")
    failed = {c.name for c in out["checks"] if not c.ok}
    assert {"grad_gap", "change_gap"} <= failed


def test_half_the_view_batch_left_out_is_caught(cache, monkeypatch):
    import repro.core.distributed as dist
    from repro.core.cameras import Camera
    orig = dist.make_gs_train_step

    def make(*a, views=None, **k):
        h = views // 2
        fn = orig(*a, views=h, **k)

        def step(g, opt, batch):
            cam = batch["cam"]
            half = {"gt_tiles": batch["gt_tiles"][:h],
                    "mask_tiles": batch["mask_tiles"][:h],
                    "cam": Camera(cam.view[:h], cam.fx[:h], cam.fy[:h],
                                  cam.width, cam.height)}
            return fn(g, opt, half)
        return step
    monkeypatch.setattr(dist, "make_gs_train_step", make)
    out, _ = tiny.run_cell("kingsnake-4m-512-train", "steady", cache,
                           impl="ref")
    assert not tiny.correct(out)
    assert not next(c for c in out["checks"] if c.name == "loss_gap").ok


def test_served_image_altered_where_rendered_is_caught(cache, monkeypatch):
    import repro.core.serving as serving
    orig = serving.render_tables_jit

    def patched(*a, **k):
        fn = orig(*a, **k)

        def call(*args):
            out = fn(*args)
            return out._replace(rgb=out.rgb.at[:, :8, :16].add(0.01))
        return call
    monkeypatch.setattr(serving, "render_tables_jit", patched)
    out, _ = tiny.run_cell("kingsnake-4m-512-serve", "orbit", cache,
                           impl="ref")
    assert not next(c for c in out["checks"]
                    if c.name == "img_mean_gap").ok


def test_cache_answering_with_another_buckets_table_is_caught(
        cache, monkeypatch):
    """A cache hit served from the table of another pose bucket."""
    import numpy as np

    from repro.core.serving import GSRenderServer
    orig = GSRenderServer._cache_get

    def wrong(self, key, rung):
        entry = orig(self, key, rung)
        others = [v for (k, r), v in self._cache.items()
                  if r == rung and k != key]
        if entry is None or not others:
            return entry
        return max(others, key=lambda v: int(np.sum(v[0] != entry[0])))
    tr = tiny.tiny_traffic("orbit")
    tr["poses"]["sessions"] = 2                 # many dwell repeats
    out, ctx = tiny.run_cell("kingsnake-4m-512-serve", "orbit", cache,
                             impl="ref", seconds=2.0, traffic=tr)
    assert tiny.correct(out), out["checks"]
    assert ctx.notes["expected_hits"] > 0
    assert any(s["hit"] for s in ctx.notes["samples"])
    monkeypatch.setattr(GSRenderServer, "_cache_get", wrong)
    out, ctx = tiny.run_cell("kingsnake-4m-512-serve", "orbit", cache,
                             impl="ref", seconds=2.0, traffic=tr)
    assert not next(c for c in out["checks"]
                    if c.name == "img_mean_gap").ok

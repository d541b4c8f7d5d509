"""Unit tests for optim/compress.py (gradient wire compression).

The module predates its first caller (core.distributed.make_gs_train_step
wires it behind GSTrainCfg.grad_compress); these tests pin its contract
directly so the driver integration can rely on it:

  * "none"  is an identity passthrough (same leaves, ratio 1.0)
  * "bf16"  is a stateless fp32->bf16->fp32 round-trip (ratio 2.0) whose
            per-element error is bounded by the bf16 unit roundoff
  * "int8"  quantises with a per-tensor scale (ratio 4.0) and CARRIES the
            residual: cumulative dequantised output over steps equals the
            cumulative true gradient minus only the final residual
  * unknown modes raise loudly

The timeseries boundary contract (PR 9) rides at the bottom: the int8
error-feedback residual must NOT cross a timestep boundary — a
``warm_start=`` resume of ``core.distributed.fit_partitions`` drops the
saved residual (the new timestep's field moved under the rows, so the
carried error is stale) and matches a per-timestep-fresh run bit-for-bit,
while a same-timestep DISK resume keeps it and diverges from both.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.optim.compress import compress_grads


def _tree(seed=0):
    k = jax.random.PRNGKey(seed)
    ka, kb = jax.random.split(k)
    return {
        "a": jax.random.normal(ka, (33, 7), jnp.float32),
        "b": 1e-3 * jax.random.normal(kb, (128,), jnp.float32),
    }


def test_none_is_identity():
    g = _tree()
    out, err, ratio = compress_grads(g, "none", err_state=None)
    assert ratio == 1.0
    assert err is None
    # identity, not a copy: the driver's "none" path must stay zero-cost
    assert out is g


def test_bf16_round_trip():
    g = _tree()
    out, err, ratio = compress_grads(g, "bf16", err_state=None)
    assert ratio == 2.0
    assert err is None           # stateless: no residual to carry
    for name in g:
        o, x = np.asarray(out[name]), np.asarray(g[name])
        assert o.dtype == np.float32   # decompressed back to f32
        # bf16 keeps f32's exponent; 8-bit mantissa -> relative error
        # <= 2^-9 per element (round-to-nearest unit roundoff)
        assert np.all(np.abs(o - x) <= np.abs(x) * 2.0 ** -8 + 1e-12)
        # and it actually quantised: exact only where bf16-representable
        assert o == pytest.approx(x, rel=2.0 ** -8)


def test_int8_error_feedback_carries_residual():
    g = _tree()
    # step 1: err_state=None must zeros-init internally
    d1, e1, ratio = compress_grads(g, "int8", err_state=None)
    assert ratio == 4.0
    for name in g:
        # per-tensor scale = max|g|/127 -> error <= scale/2 per element
        scale = float(np.abs(np.asarray(g[name])).max()) / 127.0
        assert np.abs(np.asarray(d1[name] - g[name])).max() <= 0.5 * scale \
            + 1e-7
        # residual is exactly what the wire dropped
        np.testing.assert_allclose(np.asarray(e1[name]),
                                   np.asarray(g[name] - d1[name]),
                                   rtol=0, atol=1e-7)
    # step 2 with the SAME gradient: the carried residual compensates, so
    # cumulative dequantised == cumulative true gradient - final residual
    # (the error-feedback invariant that makes long-run bias vanish)
    d2, e2, _ = compress_grads(g, "int8", err_state=e1)
    for name in g:
        lhs = np.asarray(d1[name] + d2[name] + e2[name])
        rhs = np.asarray(g[name] + g[name])
        np.testing.assert_allclose(lhs, rhs, rtol=0, atol=1e-5)


def test_int8_zero_init_matches_explicit_zeros():
    g = _tree(1)
    zeros = jax.tree.map(lambda x: jnp.zeros_like(x), g)
    d_none, e_none, _ = compress_grads(g, "int8", err_state=None)
    d_zero, e_zero, _ = compress_grads(g, "int8", err_state=zeros)
    for name in g:
        np.testing.assert_array_equal(np.asarray(d_none[name]),
                                      np.asarray(d_zero[name]))
        np.testing.assert_array_equal(np.asarray(e_none[name]),
                                      np.asarray(e_zero[name]))


def test_unknown_mode_raises():
    with pytest.raises(ValueError):
        compress_grads(_tree(), "fp4", err_state=None)


# ---------------------------------------------------------------------------
# Timestep-boundary reset (PR 9): the residual never crosses a warm start
# ---------------------------------------------------------------------------


def _scene(res=16, V=2, N=64):
    """Tiny driver scene, rebuilt from host numpy on EVERY call: the
    donating train step consumes the init buffers, so each fit_partitions
    call needs fresh device arrays."""
    from repro.core.cameras import orbital_rig
    from repro.core.gaussians import from_points
    from repro.core.pipeline import render_views
    from repro.core.tiling import TileGrid
    from repro.data.isosurface import point_cloud_for

    pts, cols = point_cloud_for("sphere_shell", N)
    pts, cols = np.array(pts[:N]), np.array(cols[:N])
    cams = orbital_rig(V, (0.5, 0.5, 0.5), 1.6, width=res, height=res)
    grid = TileGrid(res, res, 8, 8)
    g_gt = from_points(jnp.asarray(pts), jnp.asarray(cols), opacity=0.95)
    gts = np.asarray(render_views(g_gt, cams, grid, K=8, bg=0.0)[0])
    g0 = from_points(jnp.asarray(pts), jnp.asarray(cols), opacity=0.7)
    g_b = jax.tree.map(lambda x: x[None], g0)
    masks = jnp.ones((1, V, res, res), bool)
    return g_b, cams, jnp.asarray(gts)[None], masks, grid


@pytest.mark.slow
def test_int8_error_feedback_resets_at_timestep_boundary(tmp_path):
    """An int8-compressed run checkpoints (g, opt, err) with a NONZERO
    residual; resuming it via ``warm_start=`` (the timeseries boundary)
    drops that residual — bit-identical losses and params to a
    per-timestep-fresh run handed only (g, opt) — while a same-timestep
    DISK resume keeps it and diverges from both.  The divergence check is
    what gives the reset assertion teeth: the residual demonstrably
    changes the trajectory when it IS carried."""
    from repro.core.distributed import fit_partitions
    from repro.core.train import GSTrainCfg, init_opt
    from repro.launch.mesh import make_mesh
    from repro.runtime import CheckpointManager

    cfg = GSTrainCfg(K=8, lambda_dssim=0.0, bg=0.0, view_batch=1,
                     lr_colors=5e-2, grad_compress="int8")
    mesh = make_mesh((1, 1), ("part", "view"))
    key = jax.random.PRNGKey(7)

    def run(**over):
        g_b, cams, gts, masks, grid = _scene()
        return fit_partitions(g_b, cams, gts, masks, cfg, mesh=mesh,
                              extent=1.0, grid=grid, key=key,
                              schedule=cfg.tier_schedule(), **over)

    ck = CheckpointManager(str(tmp_path), keep=0)
    run(steps=3, ckpt=ck, ckpt_every=3)

    def restore():
        g_b, *_ = _scene()
        err0 = jax.tree.map(lambda p: jnp.zeros_like(p, jnp.float32),
                            g_b.trainable())
        return ck.restore(3, (g_b, init_opt(g_b), err0))

    (g3, opt3, err3), extra = restore()
    err_mag = max(float(np.abs(np.asarray(v)).max())
                  for v in jax.tree.leaves(err3))
    assert err_mag > 0.0          # the saved residual really is step state

    # timestep boundary: warm start handed the FULL (g, opt, err) tree
    _, _, l_warm = run(steps=6, warm_start=((g3, opt3, err3), extra, 3))
    # per-timestep-fresh: only (g, opt) — no residual exists to carry
    (g3b, opt3b, _), extrab = restore()
    g_f, _, l_fresh = run(steps=6, warm_start=((g3b, opt3b), extrab, 3))
    np.testing.assert_allclose(l_warm, l_fresh, rtol=0, atol=0)

    # same-timestep disk resume: residual restored -> trajectory diverges
    # once the first compressed grad lands (losses[0] predates the update)
    _, _, l_resume = run(steps=6, ckpt=ck)
    assert l_resume[0] == l_warm[0]
    assert l_resume[1:] != l_warm[1:], (l_resume, l_warm)

"""Time-series warm-start training (PR 9): the ``--timeseries`` contract.

Three surfaces, each pinned at the tolerance ISSUE 9 names:

  * warm-start parity: handing ``fit_partitions`` a previous timestep's
    merged state via ``warm_start=`` lands EXACTLY on the disk-resume
    trajectory (losses bit-equal, trainables at 1e-6) — restored
    TierSchedule caps, no init re-probe (probe calls counted), densify
    key stream fast-forwarded.  Runs as a subprocess on 4 forced host
    devices (the tests/test_distributed.py driver idiom).
  * densify_cap: a property test (hypothesis, with the tests/_hyp.py
    degraded fallback) that one densify event never grows the live count
    past ``max(cap, live_before)`` — the GeoGaussian-style ``num_max``
    bound that keeps timeseries memory flat.
  * delta checkpoints: ``save_delta``/``restore_delta`` round-trip
    exactly through a >=3-deep chain — f32, int32 and cold-quantized
    int8 leaves, schedule/exchange extras riding along — and fail LOUDLY
    when the base is missing, replaced, or structurally different;
    plain ``restore`` refuses a delta step.

The end-to-end ``--timeseries`` CLI (2 timesteps, warm-start provenance
print, committed delta manifest, restart skip-to-merge) is the slow
subprocess smoke at the bottom — the pytest twin of the CI leg.
"""

import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:                      # degraded fallback (see tests/_hyp.py)
    from _hyp import given, settings, st

from repro.core.gaussians import from_points
from repro.core.train import GSTrainCfg, densify_and_prune, init_opt
from repro.runtime import CheckpointManager
from repro.runtime.checkpoint import dequantize_cold, quantize_cold

SRC = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", "src"))


# ---------------------------------------------------------------------------
# densify_cap: live count never exceeds max(cap, live_before)
# ---------------------------------------------------------------------------


def _hot_partition(n_live, capacity, seed=0):
    """A partition where EVERY live splat is a densify candidate: uniform
    points, grad stats forced over any positive threshold."""
    rng = np.random.default_rng(seed)
    pts = jnp.asarray(rng.uniform(0.2, 0.8, (n_live, 3)), jnp.float32)
    g = from_points(pts, capacity=capacity, opacity=0.7)
    opt = init_opt(g)
    opt = opt._replace(grad_accum=jnp.ones_like(opt.grad_accum),
                       grad_count=jnp.ones_like(opt.grad_count))
    return g, opt


@settings(max_examples=25, deadline=None)
@given(st.integers(4, 48), st.integers(0, 64), st.integers(1, 64),
       st.integers(0, 80))
def test_densify_cap_bounds_live_count(n_live, free, max_new, cap):
    """Property: after one densify event with ``densify_cap=cap`` the live
    count is <= max(cap, live_before) (a cap below the current count only
    stops GROWTH — it never force-prunes) and never exceeds capacity;
    the uncapped twin on the same state grows at least as much."""
    capacity = n_live + free
    g, opt = _hot_partition(n_live, capacity)
    cfg = GSTrainCfg(K=16, max_new=max_new, densify_grad_thresh=1e-9,
                     prune_opacity=0.0, densify_cap=cap)
    g1, _ = densify_and_prune(g, opt, jax.random.PRNGKey(0), cfg, extent=1.0)
    live1 = int(np.asarray(g1.active).sum())
    assert live1 <= max(cap, n_live)
    assert live1 <= capacity
    # never below the uncapped floor semantics: cap=None grows freely
    cfg_free = GSTrainCfg(K=16, max_new=max_new, densify_grad_thresh=1e-9,
                          prune_opacity=0.0)
    g2, _ = densify_and_prune(g, opt, jax.random.PRNGKey(0), cfg_free,
                              extent=1.0)
    assert live1 <= int(np.asarray(g2.active).sum())


def test_densify_cap_admits_exact_headroom():
    """With headroom h = cap - live and >= h free slots + hot sources, the
    capped event admits EXACTLY h children (the prefix mask neither
    over- nor under-fills)."""
    g, opt = _hot_partition(16, 64)
    cfg = GSTrainCfg(K=16, max_new=32, densify_grad_thresh=1e-9,
                     prune_opacity=0.0, densify_cap=21)
    g1, _ = densify_and_prune(g, opt, jax.random.PRNGKey(0), cfg, extent=1.0)
    assert int(np.asarray(g1.active).sum()) == 21


# ---------------------------------------------------------------------------
# Delta checkpoints: exact chained round-trip + loud failure modes
# ---------------------------------------------------------------------------


def _tree(seed, n=32):
    rng = np.random.default_rng(seed)
    return {
        "f32": jnp.asarray(rng.normal(size=(n, 4)), jnp.float32),
        "i32": jnp.asarray(rng.integers(0, 9, (n,)), jnp.int32),
        "q8": jnp.asarray(rng.integers(-127, 128, (n, 3)), jnp.int8),
    }


def _perturb_rows(tree, rows, seed):
    """Touch only ``rows`` of each leaf — the timeseries shape of change."""
    rng = np.random.default_rng(seed)
    out = {}
    for k, v in tree.items():
        arr = np.array(v)
        arr[rows] = rng.normal(size=arr[rows].shape).astype(arr.dtype) \
            if arr.dtype != np.int8 else \
            rng.integers(-127, 128, arr[rows].shape).astype(np.int8)
        out[k] = jnp.asarray(arr)
    return out


def test_delta_chain_round_trips_exactly(tmp_path):
    """full @ t0 -> delta @ t1 -> delta @ t2 -> delta @ t3: every step
    restores BIT-identically (int8 leaves included), extras ride each
    manifest, and the sparse 'rows' encoding actually engaged."""
    mgr = CheckpointManager(str(tmp_path), keep=0)
    S = 4
    trees = [_tree(0)]
    for t in range(1, 4):
        trees.append(_perturb_rows(trees[-1], [1, 7, t], seed=t))

    mgr.save(S, trees[0], extra={"timestep": 0, "schedule": {"caps": [8, 4]}})
    for t in range(1, 4):
        mgr.save_delta((t + 1) * S, trees[t], base_step=t * S,
                       extra={"timestep": t,
                              "schedule": {"caps": [8, 4]},
                              "exchange": {"budget": 128 + t}})

    like = jax.tree.map(lambda x: x, trees[0])
    for t in range(4):
        got, extra = mgr.restore_delta((t + 1) * S, like)
        assert extra["timestep"] == t
        if t:
            assert extra["exchange"]["budget"] == 128 + t
        for k in trees[t]:
            a, b = np.asarray(got[k]), np.asarray(trees[t][k])
            assert a.dtype == b.dtype, k
            np.testing.assert_array_equal(a, b, err_msg=f"t={t} leaf={k}")

    # the chain really is sparse: the f32 leaf of every delta stored rows
    for t in range(1, 4):
        with open(tmp_path / f"step_{(t + 1) * S:09d}" / "manifest.json") as f:
            m = json.load(f)
        assert m["delta"]["base_step"] == t * S
        modes = [leaf["delta"] for leaf in m["leaves"]]
        assert "rows" in modes, (t, modes)


def test_delta_composes_with_cold_quantized_checkpoints(tmp_path):
    """--ckpt-quantize int8 composability: a quantize_cold'd Gaussians tree
    (int8 colors/opacity_logit) delta-chains and round-trips exactly,
    and dequantizes to the same values either side of the round trip."""
    rng = np.random.default_rng(3)
    pts = jnp.asarray(rng.uniform(0.1, 0.9, (24, 3)), jnp.float32)
    g0 = from_points(pts, capacity=32, opacity=0.7)
    q0, meta0 = quantize_cold(g0)
    g1 = g0._replace(means=g0.means.at[2].add(0.05))
    q1, meta1 = quantize_cold(g1)

    mgr = CheckpointManager(str(tmp_path), keep=0)
    mgr.save(2, q0, extra={"quant": meta0})
    mgr.save_delta(4, q1, base_step=2, extra={"quant": meta1})
    got, extra = mgr.restore_delta(4, jax.tree.map(lambda x: x, q1))
    for name in q1._fields:
        a, b = np.asarray(getattr(got, name)), np.asarray(getattr(q1, name))
        assert a.dtype == b.dtype, name
        np.testing.assert_array_equal(a, b, err_msg=name)
    assert np.asarray(got.colors).dtype == np.int8
    np.testing.assert_array_equal(
        np.asarray(dequantize_cold(got, extra["quant"]).colors),
        np.asarray(dequantize_cold(q1, meta1).colors))


def test_delta_failure_modes_are_loud(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=0)
    t0, t1 = _tree(0), _perturb_rows(_tree(0), [0], 1)

    # save_delta without a committed base
    with pytest.raises(ValueError, match="base checkpoint step 4 is missing"):
        mgr.save_delta(8, t1, base_step=4)

    mgr.save(4, t0)
    # structure mismatch vs the base
    with pytest.raises(ValueError, match="does not match"):
        mgr.save_delta(8, {"only": t1["f32"]}, base_step=4)

    mgr.save_delta(8, t1, base_step=4)
    like = jax.tree.map(lambda x: x, t0)

    # plain restore() must refuse the delta step (restore_delta's job)
    with pytest.raises(ValueError, match="DELTA checkpoint"):
        mgr.restore(8, like)

    # base replaced after the delta was written -> digest mismatch
    mgr.save(4, _perturb_rows(t0, [2], 9))
    with pytest.raises(ValueError, match="DIFFERENT base"):
        mgr.restore_delta(8, like)

    # base gone entirely -> chain refusal names the missing step
    import shutil
    shutil.rmtree(tmp_path / "step_000000004")
    with pytest.raises(ValueError, match="needs base step 4"):
        mgr.restore_delta(8, like)


# ---------------------------------------------------------------------------
# Warm-start parity vs the disk-resume oracle (4 forced host devices)
# ---------------------------------------------------------------------------

WARM_PARITY_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, r"%(src)s")
import tempfile
import jax, jax.numpy as jnp
import numpy as np

from repro.core.cameras import orbital_rig
import repro.core.distributed as D
from repro.core.gaussians import from_points
from repro.core.pipeline import render_views
from repro.core.tiling import TileGrid
from repro.core.train import GSTrainCfg, init_opt
from repro.data.isosurface import point_cloud_for
from repro.launch.mesh import make_mesh
from repro.runtime import CheckpointManager

# count schedule probes per driver run: warm start must NOT re-probe init
probes = {"n": 0}
_real_probe = D.probe_gs_schedule
def counting_probe(*a, **kw):
    probes["n"] += 1
    return _real_probe(*a, **kw)
D.probe_gs_schedule = counting_probe

N, res, V = 256, 32, 4
pts, cols = point_cloud_for("sphere_shell", N)
pts, cols = pts[:N], cols[:N]
cams = orbital_rig(V, (0.5, 0.5, 0.5), 1.6, width=res, height=res)
mesh = make_mesh((2, 2), ("part", "view"))
grid = TileGrid(res, res, 8, 16)

g_gt = from_points(jnp.asarray(pts), jnp.asarray(cols), opacity=0.95)
gts = jnp.asarray(render_views(g_gt, cams, grid, K=16, bg=0.0)[0])
masks = jnp.ones((V, res, res), bool)
g0 = from_points(jnp.asarray(pts), jnp.asarray(cols), capacity=N + 128,
                 opacity=0.7)
g_b = jax.tree.map(lambda x: x[None], g0)

cfg = GSTrainCfg(K=16, lambda_dssim=0.0, bg=0.0, view_batch=2,
                 lr_colors=5e-2, max_new=64, densify_grad_thresh=1e-9)
kw = dict(mesh=mesh, extent=1.0, densify_every=3, densify_from=0, grid=grid)

def run(**over):
    probes["n"] = 0
    out = D.fit_partitions(g_b, cams, gts[None], masks[None], cfg,
                           key=jax.random.PRNGKey(1), **kw, **over)
    return out, probes["n"]

# oracle: 0..3 with a checkpoint at 3, then disk-resume 3..6
ck = CheckpointManager(tempfile.mkdtemp(), keep=0)
(_, p_cold) = run(steps=3, ckpt=ck, ckpt_every=3,
                  schedule=cfg.tier_schedule())
sched_b = cfg.tier_schedule()
((g_r, _, l_r), p_resume) = run(steps=6, ckpt=ck, schedule=sched_b)

# warm-start: the SAME saved state handed in memory, no disk manager
tree, extra = ck.restore(3, (g_b, init_opt(g_b)))
sched_c = cfg.tier_schedule()
((g_w, _, l_w), p_warm) = run(steps=6, warm_start=(tree, extra, 3),
                              schedule=sched_c)

np.testing.assert_allclose(l_r, l_w, rtol=0, atol=0)
for k, v in g_r.trainable().items():
    np.testing.assert_allclose(np.asarray(v), np.asarray(getattr(g_w, k)),
                               rtol=0, atol=1e-6, err_msg=k)
assert sched_c.tier_caps is not None       # caps came from the warm extra
# cold run pays the init probe the resumed runs skip; warm == disk resume
assert p_cold > p_resume, (p_cold, p_resume)
assert p_warm == p_resume, (p_warm, p_resume)
print("WS-PARITY", [round(l, 5) for l in l_w])
print("WS-PROBES cold=%%d resume=%%d warm=%%d" %% (p_cold, p_resume, p_warm))

# policy guard fires on the warm path exactly like a disk resume
try:
    run(steps=6, warm_start=(tree, {"grad_compress": "int8"}, 3),
        schedule=cfg.tier_schedule())
except ValueError as e:
    assert "grad_compress" in str(e)
    print("WS-POLICY-GUARD")

# densify_cap through the driver: cap at the current live count freezes it
tree2, extra2 = ck.restore(3, (g_b, init_opt(g_b)))
live0 = int(np.asarray(tree2[0].active).sum())
((g_c, _, _), _) = run(steps=6, warm_start=(tree2, extra2, 3),
                       densify_cap=live0, schedule=cfg.tier_schedule())
live_c = int(np.asarray(g_c.active).sum())
live_w = int(np.asarray(g_w.active).sum())
assert live_c == live0 and live_w > live0, (live0, live_c, live_w)
print("WS-DENSIFY-CAP %%d -> %%d (uncapped %%d)" %% (live0, live_c, live_w))
"""


@pytest.mark.slow
def test_warm_start_matches_disk_resume(tmp_path):
    """``warm_start=`` is an in-memory resume: bit-equal losses and 1e-6
    trainables vs the disk-resume oracle, restored caps (no init probe —
    probe calls counted), resume-policy guard, and a driver-level
    densify_cap that freezes the live count where the uncapped run
    grows."""
    code = WARM_PARITY_SCRIPT % {"src": SRC}
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-3000:])
    assert "WS-PARITY" in out.stdout
    assert "WS-POLICY-GUARD" in out.stdout
    assert "WS-DENSIFY-CAP" in out.stdout


# ---------------------------------------------------------------------------
# --timeseries CLI: 2 timesteps, warm provenance, committed delta, restart
# ---------------------------------------------------------------------------


@pytest.mark.slow
def test_timeseries_cli_smoke_and_restart(tmp_path):
    """`--gs --timeseries --smoke` on 4 forced host devices: t=0 cold,
    t=1 warm-started (provenance print: schedule+exchange restored, no
    init probe), t=1 committed as a DELTA against t=0's full checkpoint;
    a rerun restarts past the complete chain straight to merge."""
    env = dict(os.environ)
    env["PYTHONPATH"] = SRC + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    base = [sys.executable, "-m", "repro.launch.train", "--gs",
            "--timeseries", "--smoke", "--host-devices", "4",
            "--steps", "4", "--timesteps", "2",
            "--ckpt-dir", str(tmp_path)]
    out = subprocess.run(base, env=env, capture_output=True, text=True,
                         timeout=900)
    assert out.returncode == 0, (out.stdout[-2000:], out.stderr[-3000:])
    assert "timestep 0: cold start" in out.stdout
    assert "warm-start from timestep 0" in out.stdout
    assert "no init probe" in out.stdout

    man = tmp_path / "timeseries" / "step_000000008" / "manifest.json"
    with open(man) as f:
        m = json.load(f)
    assert m["delta"]["base_step"] == 4
    assert m["delta"]["base_digest"]
    assert m["extra"]["timestep"] == 1

    out2 = subprocess.run(base, env=env, capture_output=True, text=True,
                          timeout=900)
    assert out2.returncode == 0, (out2.stdout[-2000:], out2.stderr[-3000:])
    assert "chain already complete at timestep 1" in out2.stdout
    assert "warm-start from timestep" not in out2.stdout

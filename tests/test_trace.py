"""The program's trace names (``core.trace``): device scopes in the op-name
metadata of the programs it runs, and host spans in a profiler trace of
the training loop and the server."""

import re
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro.core import trace
from repro.core.cameras import orbital_rig, select
from repro.core.distributed import (fit_partitions, gs_batch_specs,
                                    gs_state_specs, make_gs_train_step)
from repro.core.gaussians import from_points
from repro.core.render import assign_tables_jit, render_tables_jit
from repro.core.serving import GSRenderServer, ServeCfg
from repro.core.tiling import TileGrid
from repro.core.train import GSTrainCfg, densify_and_prune, init_opt
from repro.data.isosurface import point_cloud_for
from repro.launch.mesh import make_mesh

RES, N, V = 32, 256, 2
CENTER = (0.5, 0.5, 0.5)


def _scopes(lowered) -> set:
    """Every gs.<layer> named in a lowered program's op locations."""
    return set(re.findall(r"gs\.([a-z]+)", lowered.as_text(debug_info=True)))


def _scene():
    pts, cols = point_cloud_for("sphere_shell", N)
    g = from_points(jnp.asarray(pts[:N]), jnp.asarray(cols[:N]), opacity=0.9)
    cams = orbital_rig(V, CENTER, 1.6, width=RES, height=RES)
    return g, cams, TileGrid(RES, RES, 8, 16)


def test_scope_rejects_an_unknown_layer():
    with pytest.raises(ValueError, match="unknown layer"):
        trace.scope("assignment")


def test_the_train_step_names_its_layers():
    cfg = GSTrainCfg(K=8, view_batch=V)
    mesh = make_mesh((1, 1), ("part", "view"))
    grid = TileGrid(RES, RES, 8, 16)
    step = make_gs_train_step(mesh, cfg, grid, 1.0, views=V,
                              return_overflow=True)
    g, opt = gs_state_specs(1, N)
    lowered = step.lower(g, opt, gs_batch_specs(1, grid, views=V))
    assert _scopes(lowered) == set(trace.LAYERS) - {"densify"}
    g0, _, _ = _scene()
    densify = jax.jit(lambda g, o, k: densify_and_prune(g, o, k, cfg, 1.0))
    assert _scopes(densify.lower(g0, init_opt(g0), jax.random.PRNGKey(0))) \
        == {"densify"}


def test_the_serving_programs_name_their_layers():
    g, cams, grid = _scene()
    tables = assign_tables_jit(grid, 8, None, "dense", None)
    assert _scopes(tables.lower(g, cams)) == {"project", "assign"}
    idx, score, _ = tables(g, cams)
    render = render_tables_jit(grid, "ref", 1.0)
    assert _scopes(render.lower(g, cams, idx, score)) == \
        {"project", "gather", "raster"}


def _host_events(path: Path) -> list:
    from jax.profiler import ProfileData
    files = sorted(path.glob("**/*.xplane.pb"))
    pd = ProfileData.from_file(str(files[-1]))
    return [(e.name, dict(e.stats)) for p in pd.planes
            if p.name.startswith("/host:") for line in p.lines
            for e in line.events if e.name.startswith("gs.")]


def test_a_profiler_trace_holds_the_host_spans(tmp_path):
    from repro.runtime import CheckpointManager

    g, cams, grid = _scene()
    gts = jnp.zeros((1, V, RES, RES, 3))
    cfg = GSTrainCfg(K=8, view_batch=V)
    server = GSRenderServer(g, grid, ServeCfg(K=8, max_batch=2),
                            center=CENTER)
    jax.profiler.start_trace(str(tmp_path / "trace"))
    fit_partitions(jax.tree.map(lambda x: x[None], g), cams, gts, None, cfg,
                   mesh=make_mesh((1, 1), ("part", "view")), steps=2,
                   extent=1.0, grid=grid, densify_every=1, densify_from=1,
                   ckpt=CheckpointManager(str(tmp_path / "ckpt"), keep=1),
                   ckpt_every=1)
    rid = server.submit(select(cams, 0))
    server.flush()
    jax.profiler.stop_trace()
    events = _host_events(tmp_path / "trace")
    names = {n for n, _ in events}
    assert {"gs.fit." + s for s in ("step", "put", "build", "dispatch",
                                    "sync", "schedule", "densify",
                                    "ckpt")} <= names
    assert sorted(st["step_num"] for n, st in events
                  if n == "gs.fit.step") == [0, 1]
    assert {"gs.serve." + s for s in ("submit", "flush", "dispatch",
                                      "assign", "stage", "render",
                                      "fetch")} <= names
    sub = [st for n, st in events if n == "gs.serve.submit"]
    assert [st["rid"] for st in sub] == [rid]
    disp = [st for n, st in events if n == "gs.serve.dispatch"]
    assert [(st["rid"], st["n"], st["pad"]) for st in disp] == [(rid, 1, 1)]
    assert not any(n.startswith("bench.") for n in names)

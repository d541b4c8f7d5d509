"""The harness finds configurations, mixes and per-layer readers by name,
so a later change adds a cell with new files only; and the command refuses
to run without a chip or without the program."""

import json
import os
import shutil
import subprocess
import sys

import chipbench_tiny as tiny
import harness
import open_loop
import scene


def test_new_config_mix_and_metric_are_found_by_name(tmp_path):
    (tmp_path / "configs").mkdir()
    (tmp_path / "traffic").mkdir()
    (tmp_path / "metrics").mkdir()
    (tmp_path / "configs" / "toy.json").write_text(
        json.dumps({"kind": "train", "n_points": 7}))
    (tmp_path / "traffic" / "burst.json").write_text(
        json.dumps({"rate_rps": 3.5}))
    (tmp_path / "metrics" / "toy_share.serve.py").write_text(
        "def read(run):\n    return run.telemetry['hits'] * 2.0\n")
    bench = {"configs": [{"name": "toy",
                          "file": "configs/toy.json"}],
             "workloads": [{"name": "toy.burst", "config": "toy",
                            "traffic": "burst", "chips": 1}],
             "end_to_end": [{"name": "setup_s"}],
             "per_layer": [{"name": "toy_share.serve",
                            "workloads": ["toy.burst"]},
                           {"name": "other", "workloads": ["x"]}]}
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    b = harness.load_benchmark(tmp_path)
    cell = harness.find_cell(b, "toy.burst")
    assert harness.load_config(b, cell["config"], tmp_path)["n_points"] == 7
    assert harness.load_traffic(cell["traffic"], tmp_path)["rate_rps"] == 3.5
    names = [m["name"] for m in harness.cell_per_layer(b, "toy.burst")]
    assert names == ["toy_share.serve"]
    read = harness.metric_reader("toy_share.serve", tmp_path)

    class Run:
        telemetry = {"hits": 4}
    assert read(Run) == 8.0


def test_new_volume_and_traffic_processes_are_found_by_name(tmp_path):
    """A dataset, an arrival process and a pose process added as files of
    their own drive the generator with no edit to an existing file."""
    import numpy as np
    (tmp_path / "volumes").mkdir()
    (tmp_path / "volumes" / "ball.py").write_text(
        "def field(x, y, z):\n"
        "    return (x - .5) ** 2 + (y - .5) ** 2 + (z - .5) ** 2 - .09\n")
    pts = scene.edge_crossings("ball", 16, here=tmp_path)
    np.testing.assert_allclose(np.linalg.norm(pts - 0.5, axis=1), 0.3,
                               atol=0.02)
    for group in ("arrivals", "poses"):
        (tmp_path / "traffic" / group).mkdir(parents=True)
    (tmp_path / "traffic" / "arrivals" / "twice.py").write_text(
        "import numpy as np\n"
        "def times(p, seconds, rng):\n"
        "    return np.array([0.0, p['gap_s']])\n")
    (tmp_path / "traffic" / "poses" / "still.py").write_text(
        "import scene\n"
        "def make(p, n, fixed, rng, center, rig_radius):\n"
        "    v = scene.pose(center, rig_radius, p['azimuth'], 0.0)\n"
        "    return [(v, False)] * n, [(v, False)]\n")
    mix = {"arrivals": {"kind": "twice", "gap_s": 0.25},
           "poses": {"kind": "still", "azimuth": 1.0}, "fixed_seed": 3}
    reqs, prime = open_loop.make(mix, center=np.full(3, 0.5), rig_radius=2.0,
                                 seconds=1.0, seed=9, here=tmp_path)
    assert [r.arrival_s for r in reqs] == [0.0, 0.25] and len(prime) == 1
    np.testing.assert_array_equal(reqs[0].view, reqs[1].view)


def test_every_listed_metric_and_cell_has_its_files():
    b = harness.load_benchmark(tiny.ROOT)
    for m in b["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))
    for w in b["workloads"]:
        cfg = harness.load_config(b, w["config"], tiny.ROOT)
        assert callable(scene.volume(cfg["dataset"]))
        tr = harness.load_traffic(w["traffic"])
        for group in ("arrivals", "poses"):
            if group in tr:
                assert open_loop.kind(group, tr[group]["kind"])


def test_program_blocks_reach_the_programs_config_as_they_stand():
    import serve_cell
    import train_cell
    cfg = tiny.tiny_cfg("kingsnake-4m-512-train")
    cfg["program"].update(exchange=True, k_tiers=[8, 64])
    pcfg = train_cell.program_cfg(cfg, {"dtype_policy": "bf16"})
    assert pcfg.exchange and pcfg.k_tiers == (8, 64)
    assert pcfg.dtype_policy == "bf16" and (pcfg.tile_h, pcfg.tile_w) == (8, 16)
    cfg = tiny.tiny_cfg("kingsnake-4m-512-serve")
    cfg["server"]["shed_at"] = 3
    scfg = serve_cell.server_cfg(cfg, {})
    assert scfg.shed_at == 3 and scfg.lod_fracs == (1.0, 0.4)


def _run(cwd):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, "benchmarks/chip/run.py", "--workload",
         "ks4m-train.steady", "--seed", "3", "--seconds", "1", "--trace",
         "0"], cwd=cwd, env=env, capture_output=True, text=True,
        timeout=120)


def test_cli_exits_nonzero_without_a_tpu():
    p = _run(tiny.ROOT)
    assert p.returncode != 0 and p.stdout == ""
    assert "TPU" in p.stderr


def test_cli_exits_nonzero_without_the_program(tmp_path):
    shutil.copy(tiny.ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(tiny.CHIP, tmp_path / "benchmarks" / "chip",
                    ignore=shutil.ignore_patterns(".cache", ".trace", ".out",
                                                  ".scratch", "__pycache__"))
    p = _run(tmp_path)
    assert p.returncode != 0 and p.stdout == ""

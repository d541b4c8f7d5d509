"""chip_smoke.py and the chip-only entry points, on CPU.

The smoke's phases are rehearsed at a small size (``TINY``/``TINY4``:
interpret-mode kernels, the CPU tile) through the same functions the chip
run calls; the chip-only paths (``chip_smoke.py``, ``--full``) must refuse
to run off-TPU, with a clear message and no result line.
"""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

ROOT = Path(__file__).resolve().parents[1]
SRC = str(ROOT / "src")
sys.path.insert(0, str(ROOT))

import chip_smoke  # noqa: E402


def _run(args, cwd=ROOT, env=None, timeout=300):
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "PYTHONPATH": SRC,
           **(env or {})}
    return subprocess.run([sys.executable] + args, cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def test_one_chip_phases_rehearsal(tmp_path):
    """Train -> merge -> parity render -> two serving passes, at TINY size:
    losses finite and falling, interpret == ref, repeat pass all hits."""
    r = chip_smoke.one_chip(chip_smoke.TINY, tmp_path / "smoke")
    assert len(r["losses"]) == 7
    assert r["losses"][-1] < r["losses"][0]
    assert r["render_err"] <= chip_smoke.RENDER_ATOL
    last = r["passes"][-1]
    assert last["hits"] == last["requests"] > 0
    assert (tmp_path / "smoke" / "gs" / "merged").is_dir()


def test_kernel_phase_rehearsal():
    """The kernel parity phase, interpret vs ref at both tiles."""
    r = chip_smoke.kernels("interpret", n_tiles=8, K=16)
    assert set(r) == set(chip_smoke.TILES)
    for fwd, grad in r.values():
        assert fwd <= chip_smoke.RENDER_ATOL
        assert 0 <= grad <= chip_smoke.KERNEL_GRAD_RTOL


FOUR = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json, sys
sys.path[:0] = [%(root)r, %(src)r]
import chip_smoke
r = chip_smoke.four_chips(chip_smoke.TINY4)
print("FOUR", json.dumps({k: v["losses"] for k, v in r.items()}))
"""


def test_four_chip_phase_rehearsal():
    """2x2 all-gather and 2x2 exchange match the 1x1 mesh within the
    smoke's stated tolerances, on 4 host devices."""
    out = _run(["-c", FOUR % {"root": str(ROOT), "src": SRC}], timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [x for x in out.stdout.splitlines() if x.startswith("FOUR ")][-1]
    losses = json.loads(line[5:])
    assert set(losses) == {"all-gather", "exchange"}
    assert all(len(v) == 3 for v in losses.values())
    assert "2x2 exchange vs 1x1 params" in out.stdout


def test_chip_smoke_refuses_cpu():
    out = _run(["chip_smoke.py"])
    assert out.returncode != 0
    assert "TPU" in out.stderr
    assert '"ok"' not in out.stdout


def test_chip_smoke_alone_refuses(tmp_path):
    """Copied away from the repo, the script fails without a result."""
    shutil.copy(ROOT / "chip_smoke.py", tmp_path)
    out = _run(["chip_smoke.py"], cwd=tmp_path, env={"PYTHONPATH": ""})
    assert out.returncode != 0
    assert "checkout" in out.stderr
    assert '"ok"' not in out.stdout


@pytest.mark.parametrize("module", ["repro.launch.train",
                                    "repro.launch.serve_gs"])
def test_full_refuses_cpu(module, tmp_path):
    """--full is the chip configuration: off-TPU it exits nonzero naming
    the TPU, and never falls back to the CPU or to the ref rasterizer."""
    args = ["--gs"] if module.endswith("train") else []
    out = _run(["-m", module, *args, "--full", "--ckpt-dir",
                str(tmp_path)])
    assert out.returncode != 0
    assert "--full runs on a TPU only" in out.stderr
    assert "[train-gs]" not in out.stdout


def test_compile_cache_placement(monkeypatch):
    """JAX_COMPILATION_CACHE_DIR wins untouched; otherwise the cache goes
    to .jax_cache/ at the checkout root.  Either way the key includes the
    programs' metadata (the op names a trace reads)."""
    from repro.launch import device

    before = jax.config.jax_compilation_cache_dir
    meta = jax.config.jax_compilation_cache_include_metadata_in_key
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
        jax.config.update("jax_compilation_cache_dir", None)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          False)
        assert device.enable_compile_cache() == "/elsewhere"
        assert jax.config.jax_compilation_cache_dir is None
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = device.enable_compile_cache()
        assert path == str(ROOT / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        jax.config.update("jax_compilation_cache_include_metadata_in_key",
                          meta)

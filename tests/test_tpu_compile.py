"""The rasterizer kernels compile for a TPU v5e (described, not attached).

Interpret-mode parity (test_kernel_rasterize.py and friends) runs the same
kernel bodies on CPU but cannot see what the chip's Mosaic compiler refuses:
unaligned block shapes, vector ops with no TPU lowering.  These tests
compile ``rasterize_fwd``/``rasterize_bwd`` for one chip of a described
``v5e:2x2`` topology, at the production (8, 128) tile and at the CPU
(8, 16) tile (which runs on a TPU without ``--full``), for the tier
ladder's K values, and assert that the compiled program holds the Pallas
kernel.  Compiled through the public entry point (``kernels.ops``), each
launch keeps its kernel name and the ``gs.raster`` scope in its op name,
and still has the output layout by which the benchmark's trace reduction
finds it (``benchmarks/chip/reduce_trace.raster_pass``).

The topology is described inside a module fixture (never at import), so
only the worker that runs this file loads the TPU compiler; it skips where
no TPU compiler is installed.  The persistent compile cache is off around
these compiles: an entry for a described chip cannot be read back here.
"""

import os
import re
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.tiling import FEAT_DIM
from repro.kernels import ops
from repro.kernels import rasterize as rk
from repro.launch.device import CPU_TILE, FULL_TILE

sys.path.insert(0, str(Path(__file__).resolve().parents[1]
                       / "benchmarks" / "chip"))
import reduce_trace  # noqa: E402

N_TILES = 512
KS = pytest.mark.parametrize("K", [16, 64, 256])


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu / compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _spec(shape, sharding):
    return jax.ShapeDtypeStruct(shape, jnp.float32, sharding=sharding)


def _compile_fwd(sharding, K, tile):
    th, tw = tile
    fwd = jax.jit(lambda f, o: rk.rasterize_fwd(f, o, tile_h=th, tile_w=tw))
    return fwd.lower(_spec((N_TILES, K, FEAT_DIM), sharding),
                     _spec((N_TILES, 2), sharding)).compile().as_text()


def _compile_bwd(sharding, K, tile):
    th, tw = tile
    bwd = jax.jit(lambda f, o, out, g: rk.rasterize_bwd(
        f, o, out, g, tile_h=th, tile_w=tw))
    planes = _spec((N_TILES, 4, th, tw), sharding)
    return bwd.lower(_spec((N_TILES, K, FEAT_DIM), sharding),
                     _spec((N_TILES, 2), sharding),
                     planes, planes).compile().as_text()


@KS
def test_rasterize_fwd_compiles_for_v5e(one_chip, K):
    assert "tpu_custom_call" in _compile_fwd(one_chip, K, FULL_TILE)


@KS
def test_rasterize_bwd_compiles_for_v5e(one_chip, K):
    assert "tpu_custom_call" in _compile_bwd(one_chip, K, FULL_TILE)


@KS
def test_rasterize_fwd_compiles_for_v5e_cpu_tile(one_chip, K):
    assert "tpu_custom_call" in _compile_fwd(one_chip, K, CPU_TILE)


@KS
def test_rasterize_bwd_compiles_for_v5e_cpu_tile(one_chip, K):
    assert "tpu_custom_call" in _compile_bwd(one_chip, K, CPU_TILE)


def _kernel_launches(text):
    """{pass: op name} of each Pallas launch in a compiled HLO text."""
    out = {}
    for line in text.splitlines():
        op = line.strip().removeprefix("ROOT ")
        kind = reduce_trace.raster_pass(op)
        if kind is not None:
            m = re.search(r'op_name="([^"]*)"', op)
            out[kind] = m.group(1) if m else ""
    return out


def test_named_kernels_keep_their_trace_keys(one_chip):
    """Forward alone, then a gradient (forward + backward launch)."""
    th, tw = FULL_TILE
    raster = lambda f, o: ops.rasterize_tiles(f, o, tile_h=th, tile_w=tw,
                                              impl="pallas")
    args = (_spec((N_TILES, 64, FEAT_DIM), one_chip),
            _spec((N_TILES, 2), one_chip))
    fwd = _kernel_launches(jax.jit(raster).lower(*args).compile().as_text())
    grad = jax.jit(jax.grad(lambda f, o: (raster(f, o) ** 2).sum()))
    both = _kernel_launches(grad.lower(*args).compile().as_text())
    assert set(fwd) == {"fwd"} and set(both) == {"fwd", "bwd"}
    for kind, name in (("fwd", fwd["fwd"]), ("bwd", both["bwd"])):
        assert "gs.raster" in name and f"raster_{kind}" in name, name

"""Chip-path settings shared by the drivers and ``chip_smoke.py``.

``--full`` means the paper's sizes on the chip: the production (8, 128)
tile and the compiled Pallas kernels.  It never falls back to the CPU or to
the ``ref`` rasterizer — ``require_tpu`` fails first.

Persistent compile cache: ``enable_compile_cache`` leaves an exported
``JAX_COMPILATION_CACHE_DIR`` to JAX (which reads it itself) and otherwise
points the cache at ``.jax_cache/`` in the checkout root — a fixed path, so
a later process of the same checkout finds what an earlier one compiled.
The cache key includes the programs' metadata: the op names a device trace
attributes to ``gs.*`` scopes come from it, and a program that differs from
a cached one only in metadata must not load the cached op names.

Importing this module does not import jax (the drivers set ``XLA_FLAGS``
before their first jax import).
"""

from __future__ import annotations

import os
from pathlib import Path

#: production tile (one f32 VREG per accumulator plane) and kernel impl
FULL_TILE = (8, 128)
FULL_IMPL = "pallas"
#: CPU tile: keeps small test images many tiles wide
CPU_TILE = (8, 16)

CHECKOUT_ROOT = Path(__file__).resolve().parents[3]
CACHE_DIR = CHECKOUT_ROOT / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compile cache -> the directory it uses."""
    import jax
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)


def require_tpu(what: str):
    """Raise SystemExit unless JAX's first device is a TPU -> that device."""
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"{what} runs on a TPU only, but JAX's first device is "
            f"{dev.platform!r} ({dev.device_kind}); there is no CPU or "
            "ref-rasterizer fallback — run the small CPU configurations "
            "without --full instead")
    return dev

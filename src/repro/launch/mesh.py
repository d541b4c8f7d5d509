"""Mesh construction: the one place this repo builds a ``jax.sharding.Mesh``.

Defined as FUNCTIONS (never module-level constants) so importing this module
never touches jax device state — required because the dry-run forces 512
host devices while tests/benches must see 1 (assignment, MULTI-POD DRY-RUN
step 1).

Every axis is ``AxisType.Auto``.  Since jax 0.8 ``jax.make_mesh`` defaults
to Explicit axes, under which sharding propagation is part of the type
system: the vmapped densify's fixed-size ``jnp.nonzero`` lowers to a scatter
whose sharding check then fails ("Resource axis: part of
PartitionSpec('part',) is not found in mesh").  The trainer is written for
Auto axes (shard_map bodies + jit in/out shardings), so meshes are built
here and ``repro.core.distributed.check_auto_mesh`` refuses anything else
at the trainer's entry.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape, axes, *, devices=None):
    """Mesh of ``shape`` over named ``axes``, every axis Auto.

    ``devices`` (default: ``jax.devices()``) pins the device set, e.g. a
    1x1 mesh on ``jax.devices()[:1]`` next to a 2x2 mesh in one process."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)

"""The program's trace vocabulary: one name per layer, on the device and on
the host.

``scope(layer)`` is a ``jax.named_scope("gs.<layer>")``: it lands in the
op-name metadata of every op traced inside it (a backward op keeps it inside
``transpose(jvp(...))``), so a device trace attributes op time to layers.
It is metadata only; the compiled program does not change.

``span(name, **attrs)`` is a profiler ``TraceAnnotation("gs.<name>")`` on
the host: it costs nothing when no profiler runs, and when one does it
shares the device trace's clock.  ``attrs`` are ints the caller holds.
``step_span(i)`` marks one iteration of the training loop.

No name starts with ``bench.``, the benchmark's own prefix.
"""

from __future__ import annotations

import jax

LAYERS = ("project", "transport", "assign", "gather", "raster", "loss",
          "adam", "densify")


def scope(layer: str):
    """Device scope ``gs.<layer>``; usable as a ``with`` or a decorator."""
    if layer not in LAYERS:
        raise ValueError(f"unknown layer {layer!r}; one of {LAYERS}")
    return jax.named_scope("gs." + layer)


def span(name: str, **attrs):
    """Host span ``gs.<name>`` with integer attributes."""
    return jax.profiler.TraceAnnotation("gs." + name, **attrs)


def step_span(i: int):
    """One iteration of ``fit_partitions``' loop, as a profiler step."""
    return jax.profiler.StepTraceAnnotation("gs.fit.step", step_num=i)

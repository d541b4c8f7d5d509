"""Harness core: finds a cell's configuration, traffic mix and per-layer
metric readers by name, counts compiles and opens host spans.

Everything that belongs to one configuration, mix or metric lives in a file
of its own beside this module:

- ``configs/<config>.json``: one deployment; its ``kind`` names the driver
  module that runs it (``train_cell`` or ``serve_cell``), its ``program``
  or ``server`` block goes to the program's config class as it stands;
- ``volumes/<dataset>.py``: the analytic field of a dataset (``scene.py``);
- ``traffic/<mix>.json``: the parameters of a mix; a serving mix names an
  arrival and a pose process, each a file ``traffic/arrivals/<kind>.py`` or
  ``traffic/poses/<kind>.py`` (``open_loop.py``); a training mix holds the
  checked steps and ``fit_partitions``' schedule arguments;
- ``metrics/<metric>.py``: one reader, ``read(run) -> float | None``.

``BENCHMARK.json`` at the checkout root maps each workload to its config and
mix and lists which per-layer metrics each workload reports.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]


def log(msg: str):
    print(f"[chipbench] {msg}", file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# Registry: everything by name
# ---------------------------------------------------------------------------


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def find_cell(bench: dict, workload: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == workload:
            return w
    raise SystemExit(f"no workload named {workload!r} in BENCHMARK.json; "
                     f"have {[w['name'] for w in bench['workloads']]}")


def load_config(bench: dict, name: str, root: Path = ROOT) -> dict:
    for c in bench["configs"]:
        if c["name"] == name:
            return json.loads((root / c["file"]).read_text())
    raise SystemExit(f"no config named {name!r} in BENCHMARK.json")


def load_traffic(name: str, here: Path = HERE) -> dict:
    path = here / "traffic" / f"{name}.json"
    if not path.exists():
        raise SystemExit(f"no traffic mix {path}")
    return json.loads(path.read_text())


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, here: Path = HERE) -> Callable:
    path = here / "metrics" / f"{name}.py"
    if not path.exists():
        raise SystemExit(f"no reader {path} for per-layer metric {name!r}")
    return load_module(path).read


def cell_per_layer(bench: dict, workload: str) -> List[dict]:
    """The per-layer metric entries the cell reports."""
    return [m for m in bench["per_layer"]
            if workload in m.get("workloads", [workload])]


def peaks(device_kind: str, here: Path = HERE) -> dict:
    table = json.loads((here / "peaks.json").read_text())
    if device_kind not in table["devices"]:
        raise SystemExit(f"no published peaks for device kind "
                         f"{device_kind!r} in peaks.json")
    return table["devices"][device_kind]


# ---------------------------------------------------------------------------
# Instruments
# ---------------------------------------------------------------------------


class CompileCounter:
    """Backend compiles and persistent-cache hits, with their seconds, from
    jax.monitoring; ``mark()`` snapshots the counts."""

    def __init__(self):
        import jax
        self.compiles, self.compile_s = 0, 0.0
        self.cache_hits, self.cache_load_s = 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._dur)
        jax.monitoring.register_event_listener(self._event)

    def _dur(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1
            self.compile_s += duration
        elif event == "/jax/compilation_cache/cache_retrieval_time_sec":
            self.cache_load_s += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def mark(self) -> dict:
        return {"compiles": self.compiles, "compile_s": self.compile_s,
                "cache_hits": self.cache_hits,
                "cache_load_s": self.cache_load_s}


def span(name: str):
    """A host span around the benchmark's own call into a layer; it lands
    in the profiler's trace when one runs, where the reduction labels idle
    gaps by it."""
    import jax
    return jax.profiler.TraceAnnotation(name)


@dataclasses.dataclass
class Ctx:
    """What a kind driver gets: the cell, the seed and the instruments."""
    workload: str
    cfg: dict
    traffic: dict
    seed: int
    seconds: float
    trace: bool
    compiles: Optional[CompileCounter] = None
    overrides: dict = dataclasses.field(default_factory=dict)
    #: set by the driver: seconds of set-up by part, diagnostics
    setup_parts: dict = dataclasses.field(default_factory=dict)
    notes: dict = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Check:
    """One number compared, beside its limit (passes when value <= limit)."""
    name: str
    value: float
    limit: float

    @property
    def ok(self) -> bool:
        return self.value == self.value and self.value <= self.limit


def device_line(chips: int) -> dict:
    import jax
    devs = jax.devices()
    used = devs[:chips]
    peak = 0
    for d in used:
        st = d.memory_stats() or {}
        peak = max(peak, int(st.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(used), "memory_peak_bytes": peak}

"""Trace-to-metrics reduction, kept with the benchmark so every PR computes
the same numbers the same way.

``load`` reads the profiler's ``.xplane.pb`` (``jax.profiler.ProfileData``)
into plain interval lists: device ops and XLA modules per TPU plane, and
the host spans the benchmark annotated (names starting ``bench.``).
``reduce`` works on those lists only, so it is tested on small recorded
traces without a chip:

- device busy is the union of the op intervals inside the window, per chip,
  averaged over the chips; the idle share is 1 - busy / window;
- a kernel's time is the sum of the durations of its ops (every tier's
  launch counts); op names are the HLO instruction text, and the Pallas
  rasterizer's launches are the ``tpu_custom_call`` ops, the forward pass
  writing 4 image planes per tile and the backward 16 feature gradients per
  listed splat (``raster_pass``);
- a program's time is the sum of its XLA module events, matched by name;
- ``breakdown`` holds the ops that took most time, summed by instruction
  name (a ``while`` holds the ops of its body), and the longest idle gaps,
  each labelled by the benchmark span that overlaps it most (``host``
  where none does).
"""

from __future__ import annotations

import glob
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

Interval = Tuple[str, int, int]          # (name, start_ns, end_ns)

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"


_SHAPE = re.compile(r"^%\S+ = f32\[([0-9,]+)\]")


def short_name(op: str) -> str:
    """'%fusion.3 = f32[8]{0} fusion(...), ...' -> '%fusion.3 fusion'."""
    head, _, rest = op.partition(" = ")
    m = re.search(r"[\]\})]\s([a-z][a-z0-9-]*)\(", rest)
    return f"{head} {m.group(1)}" if m else head


def raster_pass(op: str):
    """'fwd' or 'bwd' for a launch of the Pallas rasterizer, else None:
    the forward returns f32[T, 4, th, tw], the backward f32[T, K, 16]."""
    if "tpu_custom_call" not in op or " custom-call(" not in op:
        return None
    m = _SHAPE.match(op)
    if not m:
        return None
    dims = [int(d) for d in m.group(1).split(",")]
    if len(dims) == 4 and dims[1] == 4:
        return "fwd"
    if len(dims) == 3 and dims[2] == 16:
        return "bwd"
    return None


def find_xplane(trace_dir: Path) -> Optional[Path]:
    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    return Path(files[-1]) if files else None


def load(path: Path) -> dict:
    """-> {"ops": {plane: [Interval]}, "modules": {plane: [Interval]},
    "host": [Interval]} (benchmark spans only)."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    ops: Dict[str, List[Interval]] = {}
    modules: Dict[str, List[Interval]] = {}
    host: List[Interval] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    dst = ops.setdefault(plane.name, [])
                elif line.name == MODULES_LINE:
                    dst = modules.setdefault(plane.name, [])
                else:
                    continue
                for e in line.events:
                    s = int(e.start_ns)
                    dst.append((e.name, s, s + int(e.duration_ns)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        s = int(e.start_ns)
                        host.append((e.name, s, s + int(e.duration_ns)))
    return {"ops": ops, "modules": modules, "host": host}


def union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: int, hi: int):
    for iv in intervals:
        s, e = max(iv[-2], lo), min(iv[-1], hi)
        if e > s:
            yield iv[:-2] + (s, e) if len(iv) > 2 else (s, e)


def window_of(tr: dict) -> Tuple[int, int]:
    """The benchmark's window span, else the extent of the device ops."""
    spans = [iv for iv in tr["host"] if iv[0] == WINDOW_SPAN]
    if spans:
        return spans[0][1], spans[0][2]
    allops = [iv for v in tr["ops"].values() for iv in v]
    return min(i[1] for i in allops), max(i[2] for i in allops)


def reduce(tr: dict, *, top: int = 10) -> dict:
    """-> {"window_s", "busy_s" (mean over chips), "idle_share",
    "device_ops": [[name, s]], "idle_gaps": [[label, s]], "op_s": {name:
    s}, "module_s": {name: s}, "chips": n}."""
    lo, hi = window_of(tr)
    window = (hi - lo) * 1e-9
    busy, gaps = [], []
    op_s: Dict[str, float] = {}
    module_s: Dict[str, float] = {}
    planes = sorted(tr["ops"])
    for plane in planes:
        ivs = list(clip(tr["ops"][plane], lo, hi))
        for name, s, e in ivs:
            op_s[name] = op_s.get(name, 0.0) + (e - s) * 1e-9
        u = union([(s, e) for _, s, e in ivs])
        busy.append(sum(e - s for s, e in u) * 1e-9)
        prev = lo
        for s, e in u + [(hi, hi)]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
    for plane, ivs in tr["modules"].items():
        for name, s, e in clip(ivs, lo, hi):
            module_s[name] = module_s.get(name, 0.0) + (e - s) * 1e-9
    spans = [iv for iv in tr["host"] if iv[0] != WINDOW_SPAN]
    labelled = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, label = 0, "host"
        for name, hs, he in spans:
            ov = min(e, he) - max(s, hs)
            if ov > best:
                best, label = ov, name
        labelled.append([label, (e - s) * 1e-9])
    busy_s = sum(busy) / len(busy) if busy else 0.0
    by_short: Dict[str, float] = {}
    for name, sec in op_s.items():
        k = short_name(name)
        by_short[k] = by_short.get(k, 0.0) + sec
    return {
        "window_s": window, "busy_s": busy_s, "chips": len(planes),
        "idle_share": 1.0 - busy_s / window if window > 0 else None,
        "device_ops": [[n, s] for n, s in sorted(
            by_short.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": labelled, "op_s": op_s, "module_s": module_s}


def kernel_seconds(red: dict, kind: str) -> float:
    """Summed device time of the rasterizer's ``kind`` pass launches."""
    return sum(s for n, s in red["op_s"].items() if raster_pass(n) == kind)


def module_seconds(red: dict, prefix: str) -> float:
    return sum(s for n, s in red["module_s"].items() if n.startswith(prefix))

"""Per-layer readings from the program's own trace names (``gs.*``).

The program names its layers (``repro.core.trace``): device ops carry a
``gs.<layer>`` scope in their op-name metadata, and its host loops open
``gs.*`` profiler spans.  This module reads both from the window's trace:

- **self time per scope**: an op's self time is its duration less the ops
  nested inside it on the same line (a ``while`` counts once, its body ops
  for themselves); it goes to the op's innermost ``gs.<layer>``, else to
  ``unscoped``.  Sums are averaged over chips;
- **idle time per host span**: idle is the window less the union of op
  intervals, per chip, as ``reduce_trace`` computes it; each idle
  nanosecond goes to the innermost ``gs.*`` span open at that instant, else
  to ``none``.  Averaged over chips.

``load`` turns a ``.xplane.pb`` into plain lists; ``self_seconds`` and
``idle_seconds`` work on lists only, so they are tested on synthetic
intervals.  ``for_run`` is what the metric readers call: it takes the newest
trace under ``.trace/``, accepts it only if its ``bench.window`` is the
window the reducer measured, memoizes it by path and logs both tables.  On
a program without ``gs.*`` names the tables hold no ``gs.*`` entry, and
the readers return None.
"""

from __future__ import annotations

import glob
import os
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

import reduce_trace
from harness import log

HERE = Path(__file__).resolve().parent
TRACE_ROOT = HERE / ".trace"

#: the event-metadata stat that holds a device op's op-name metadata, e.g.
#: ``jit(step)/transpose(jvp())/gs.raster/gs.gather/scatter-add:``
PATH_STAT = "tf_op"
_LAYER = re.compile(r"(?:^|[/(])gs\.([a-z]+)(?=[/):]|$)")

Interval = Tuple[str, int, int]          # (label, start_ns, end_ns)


def layer_of(path: str) -> str:
    """Innermost ``gs.<layer>`` of an op's scope path, else 'unscoped'."""
    found = _LAYER.findall(path or "")
    return found[-1] if found else "unscoped"


_XSPACE = None


def _xspace():
    """The profiler's XSpace message, only the fields read here.

    ``jax.profiler.ProfileData`` gives an event's own stats but not those
    of its event metadata, where a device plane keeps each op's op-name
    metadata; so the file is parsed as a protobuf with this schema
    (field numbers of ``xplane.proto``; a map is a repeated entry on the
    wire)."""
    global _XSPACE
    if _XSPACE is None:
        from google.protobuf import descriptor_pb2 as d
        from google.protobuf import descriptor_pool, message_factory
        F = d.FieldDescriptorProto
        fd = d.FileDescriptorProto(name="program_trace_xspace.proto",
                                   package="program_trace", syntax="proto3")
        scalar = {"i": F.TYPE_INT64, "u": F.TYPE_UINT64, "s": F.TYPE_STRING}
        schema = {
            "XStat": "metadata_id 1 i, int64_value 4 i, str_value 5 s, "
                     "ref_value 7 u",
            "XEvent": "metadata_id 1 i, offset_ps 2 i, duration_ps 3 i, "
                      "stats 4 *XStat",
            "XLine": "name 2 s, timestamp_ns 3 i, events 4 *XEvent",
            "XEventMetadata": "id 1 i, name 2 s, stats 5 *XStat",
            "XStatMetadata": "id 1 i, name 2 s",
            "EventEntry": "key 1 i, value 2 XEventMetadata",
            "StatEntry": "key 1 i, value 2 XStatMetadata",
            "XPlane": "name 2 s, lines 3 *XLine, event_metadata 4 "
                      "*EventEntry, stat_metadata 5 *StatEntry",
            "XSpace": "planes 1 *XPlane"}
        for name, fields in schema.items():
            m = fd.message_type.add(name=name)
            for field in fields.split(", "):
                fname, num, typ = field.split()
                f = m.field.add(name=fname, number=int(num),
                                label=F.LABEL_REPEATED if typ[0] == "*"
                                else F.LABEL_OPTIONAL)
                typ = typ.lstrip("*")
                if typ in scalar:
                    f.type = scalar[typ]
                else:
                    f.type, f.type_name = F.TYPE_MESSAGE, \
                        ".program_trace." + typ
        pool = descriptor_pool.DescriptorPool()
        pool.Add(fd)
        _XSPACE = message_factory.GetMessageClass(
            pool.FindMessageTypeByName("program_trace.XSpace"))
    return _XSPACE


def _stat_values(stats, names: Dict[int, str]) -> Dict[str, object]:
    out = {}
    for st in stats:
        key = names.get(st.metadata_id, "")
        if st.str_value:
            out[key] = st.str_value
        elif st.ref_value:
            out[key] = names.get(st.ref_value, "")
        else:
            out[key] = st.int64_value
    return out


def load(path: Path) -> dict:
    """-> {"ops": {plane: [(layer, s, e)]}, "spans": [(name, s, e)],
    "window": (s, e) or None}: the TPU planes' ``XLA Ops`` events by
    innermost scope, the host's ``gs.*`` spans and ``bench.window``, in
    integer ns as ``ProfileData`` gives them (``reduce_trace.load``)."""
    space = _xspace().FromString(Path(path).read_bytes())
    ops: Dict[str, List[Interval]] = {}
    spans: List[Interval] = []
    window = None
    for plane in space.planes:
        device = plane.name.startswith("/device:TPU:")
        if not device and not plane.name.startswith("/host:"):
            continue
        names = {m.key: m.value.name for m in plane.stat_metadata}
        meta = {m.key: m.value for m in plane.event_metadata}
        layers: Dict[int, str] = {}
        for line in plane.lines:
            if device and line.name != reduce_trace.OPS_LINE:
                continue
            dst = ops.setdefault(plane.name, []) if device else spans
            for ev in line.events:
                s = line.timestamp_ns + ev.offset_ps // 1000
                e = s + ev.duration_ps // 1000
                md = meta.get(ev.metadata_id)
                name = md.name if md is not None else ""
                if device:
                    layer = layers.get(ev.metadata_id)
                    if layer is None:
                        stats = _stat_values(md.stats, names) if md else {}
                        layer = layers[ev.metadata_id] = layer_of(
                            str(stats.get(PATH_STAT, "")))
                    dst.append((layer, s, e))
                elif name.startswith("gs."):
                    spans.append((name, s, e))
                elif name == reduce_trace.WINDOW_SPAN and window is None:
                    window = (s, e)
    return {"ops": ops, "spans": spans, "window": window}


def self_seconds(ops: Sequence[Interval], lo: int, hi: int) -> Dict[str, float]:
    """{layer: seconds} of self time of one line's ops inside [lo, hi]."""
    ivs = sorted(reduce_trace.clip(ops, lo, hi), key=lambda o: (o[1], -o[2]))
    out: Dict[str, float] = {}
    stack: List[list] = []              # [label, start, end, nested_ns]

    def close():
        label, s, e, nested = stack.pop()
        out[label] = out.get(label, 0.0) + (e - s - nested) * 1e-9

    for label, s, e in ivs:
        while stack and stack[-1][2] <= s:
            close()
        if stack:
            stack[-1][3] += min(e, stack[-1][2]) - s
        stack.append([label, s, e, 0])
    while stack:
        close()
    return out


def _segments(spans: Sequence[Interval], lo: int, hi: int):
    """[(s, e, label)]: [lo, hi] cut at every span boundary, each piece
    labelled by the innermost span covering it (the latest to open)."""
    ivs = list(reduce_trace.clip(spans, lo, hi))
    cuts = sorted({lo, hi, *(s for _, s, _ in ivs), *(e for _, _, e in ivs)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        best = None
        for name, s, e in ivs:
            if s <= a and e >= b and (best is None or s > best[1]
                                      or (s == best[1] and e < best[2])):
                best = (name, s, e)
        out.append((a, b, best[0] if best else "none"))
    return out


def idle_seconds(ops: Sequence[Interval], spans: Sequence[Interval],
                 lo: int, hi: int) -> Dict[str, float]:
    """{span name or 'none': seconds} of one chip's idle time in [lo, hi]."""
    busy = reduce_trace.union([(s, e) for _, s, e in
                               reduce_trace.clip(ops, lo, hi)])
    gaps, prev = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    out: Dict[str, float] = {}
    segs = _segments(spans, lo, hi)
    j = 0
    for gs_, ge in gaps:
        while j < len(segs) and segs[j][1] <= gs_:
            j += 1
        k = j
        while k < len(segs) and segs[k][0] < ge:
            a, b, label = segs[k]
            ov = min(b, ge) - max(a, gs_)
            if ov > 0:
                out[label] = out.get(label, 0.0) + ov * 1e-9
            k += 1
    return out


def _mean(tables: List[Dict[str, float]]) -> Dict[str, float]:
    out: Dict[str, float] = {}
    for t in tables:
        for k, v in t.items():
            out[k] = out.get(k, 0.0) + v / len(tables)
    return out


def reduce(tr: dict) -> dict:
    """-> {"window_s", "scope_s": {layer: s}, "idle_s": {span: s},
    "spans": names of the spans in the window}, times averaged over the
    trace's chips."""
    lo, hi = tr["window"]
    planes = sorted(tr["ops"])
    return {
        "window_s": (hi - lo) * 1e-9,
        "spans": sorted({n for n, _, _ in
                         reduce_trace.clip(tr["spans"], lo, hi)}),
        "scope_s": _mean([self_seconds(tr["ops"][p], lo, hi)
                          for p in planes]),
        "idle_s": _mean([idle_seconds(tr["ops"][p], tr["spans"], lo, hi)
                         for p in planes])}


def find_newest(root: Path = TRACE_ROOT) -> Optional[Path]:
    files = glob.glob(str(Path(root) / "**" / "*.xplane.pb"), recursive=True)
    return Path(max(files, key=os.path.getmtime)) if files else None


_MEMO: Dict[str, Optional[dict]] = {}


def for_run(run, root: Path = TRACE_ROOT) -> Optional[dict]:
    """The reduced tables of the run's trace, or None when there is no
    trace or its window is not the one ``run.red`` measured."""
    path = find_newest(root)
    if path is None:
        return None
    key = str(path)
    if key not in _MEMO:
        tr = load(path)
        _MEMO[key] = reduce(tr) if tr["window"] else None
        if _MEMO[key] is not None:
            t = _MEMO[key]
            log("program_trace scope_s: " + _fmt(t["scope_s"]))
            log("program_trace idle_s: " + _fmt(t["idle_s"]))
    t = _MEMO[key]
    if t is None or t["window_s"] != run.red["window_s"]:
        return None
    return t


def _fmt(table: Dict[str, float]) -> str:
    return ", ".join(f"{k} {v:.6f}" for k, v in
                     sorted(table.items(), key=lambda kv: -kv[1]))


def idle_under(t: dict, prefix: str) -> Optional[float]:
    """Idle seconds under spans whose name starts with ``prefix``; None
    when the trace holds no such span (a program without these names)."""
    if not any(n.startswith(prefix) for n in t["spans"]):
        return None
    return sum(v for k, v in t["idle_s"].items() if k.startswith(prefix))

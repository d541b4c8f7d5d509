"""Run one benchmark cell once on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

From the root of a checkout.  Set-up (scene, ground truth or model, server,
warm-up of every program the cell uses) counts as ``setup_s``; then the
cell measures for ``--seconds`` and checks what its timed path produced
against the plain reference (``reference.py``).  ``--trace 1`` profiles the
window and reports the cell's per-layer metrics instead of the end-to-end
ones.  Diagnostics go to standard error and to ``.out/`` beside this file;
the numbers compared, each with its limit, are the last lines on standard
error; the last line on standard output is the result:

    {"correct": ..., "attempted": ..., "failed": ..., "metrics": {...},
     "device": {...}, ["breakdown": {...},] "checks": {...}}

It exits nonzero, printing no result, when JAX finds no TPU or fewer chips
than the cell asks for, or when the checkout has no program to run.
``--dtype-policy bf16`` runs the program's lower-precision path: the
control, which must come out not correct.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))
# the TPU runtime would otherwise write its logs to a fixed /tmp path
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import harness  # noqa: E402
from harness import log  # noqa: E402


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dtype-policy", default=None, choices=("f32", "bf16"),
                    help="control runs only: the program's storage dtype")
    return ap.parse_args(argv)


def enable_cache(root: Path):
    """JAX's persistent compile cache at the program's fixed checkout path
    (``JAX_COMPILATION_CACHE_DIR`` when set), every program kept."""
    import jax

    from repro.launch.device import enable_compile_cache
    path = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


def window_hooks(ctx, trace_dir):
    """Start/end of the measured window: compile marks, the garbage
    collector and, when traced, the profiler and the ``bench.window`` span.

    Set-up leaves millions of Python objects behind (traced programs, the
    scene); a full collection that walks them stalls the process for
    hundreds of milliseconds.  They are collected and frozen before the
    window, so a collection inside it walks only what the window made;
    each collection in the window is logged with its duration."""
    import jax
    state = {}
    gcs = []

    def watch(phase, info):
        if phase == "start":
            state["gc0"] = time.perf_counter()
        else:
            gcs.append((info["generation"],
                        time.perf_counter() - state["gc0"]))

    def start():
        t = time.perf_counter()
        gc.collect()
        gc.freeze()
        ctx.notes["gc_frozen"] = {"objects": gc.get_freeze_count(),
                                  "seconds": time.perf_counter() - t}
        gc.callbacks.append(watch)
        if ctx.trace:
            jax.profiler.start_trace(str(trace_dir))
            state["ann"] = jax.profiler.TraceAnnotation("bench.window")
            state["ann"].__enter__()
        ctx.notes["compiles_setup"] = ctx.compiles.mark()

    def end():
        ctx.notes["compiles_end"] = ctx.compiles.mark()
        gc.callbacks.remove(watch)
        ctx.notes["gc_in_window"] = {
            "collections": len(gcs),
            "full": sum(g == 2 for g, _ in gcs),
            "max_ms": max((d for _, d in gcs), default=0.0) * 1e3}
        if ctx.trace:
            state["ann"].__exit__(None, None, None)
            jax.profiler.stop_trace()
    return start, end


def per_layer(bench, workload, out, ctx, trace_dir, device_kind):
    """Reduce the trace and read each per-layer metric of the cell."""
    import reduce_trace
    path = reduce_trace.find_xplane(trace_dir)
    if path is None:
        raise RuntimeError(f"no trace under {trace_dir}")
    red = reduce_trace.reduce(reduce_trace.load(path))
    run = types.SimpleNamespace(
        red=red, work=out.get("work"), peak=harness.peaks(device_kind),
        metrics=out["metrics"], telemetry=out.get("telemetry"),
        notes=ctx.notes, window_s=out["window_s"], chips=red["chips"] or 1)
    metrics = {}
    for m in harness.cell_per_layer(bench, workload):
        v = harness.metric_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return metrics, red


def main(argv=None) -> int:
    args = parse_args(argv)
    root = harness.ROOT
    if not (root / "src" / "repro").is_dir():
        log(f"no program under {root / 'src' / 'repro'}; run from a "
            "checkout of the repository")
        return 2
    bench = harness.load_benchmark(root)
    cell = harness.find_cell(bench, args.workload)
    cfg = harness.load_config(bench, cell["config"], root)
    traffic = harness.load_traffic(cell["traffic"])
    sys.path.insert(0, str(root / "src"))

    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < cell["chips"]:
        log(f"{args.workload} needs {cell['chips']} TPU chip(s); JAX has "
            f"{len(devs)} {devs[0].platform} device(s) "
            f"({devs[0].device_kind}); there is no CPU fallback")
        return 3
    kind = devs[0].device_kind
    harness.peaks(kind)                 # an unknown device is an error
    cache = enable_cache(root)

    ctx = harness.Ctx(workload=args.workload, cfg=cfg, traffic=traffic,
                      seed=args.seed, seconds=args.seconds,
                      trace=bool(args.trace))
    if args.dtype_policy:
        ctx.overrides["dtype_policy"] = args.dtype_policy
    ctx.compiles = harness.CompileCounter()
    trace_dir = HERE / ".trace" / args.workload
    shutil.rmtree(trace_dir, ignore_errors=True)
    ctx.notes["trace_hooks"] = window_hooks(ctx, trace_dir)
    log(f"{args.workload}: config {cell['config']}, traffic "
        f"{cell['traffic']}, seed {args.seed}, {args.seconds:g} s, trace "
        f"{args.trace}; {len(devs)} x {kind}; compile cache {cache}")

    mod = importlib.import_module(f"{cfg['kind']}_cell")
    out = mod.run(ctx)
    setup_s = ctx.notes["setup_end"] - T_START

    metrics = {}
    result_device = dict(out["device"])
    breakdown = None
    if args.trace:
        metrics, red = per_layer(bench, args.workload, out, ctx, trace_dir,
                                 kind)
        result_device["busy_s"] = red["busy_s"]
        result_device["window_s"] = red["window_s"]
        breakdown = {"device_ops": red["device_ops"],
                     "idle_gaps": red["idle_gaps"]}
        ctx.notes["trace"] = {k: red[k] for k in
                              ("window_s", "busy_s", "idle_share", "chips")}
        ctx.notes["module_s"] = red["module_s"]
        shutil.rmtree(trace_dir, ignore_errors=True)
    else:
        units = {m["name"]: m["unit"] for m in bench["end_to_end"]}
        metrics["setup_s"] = {"value": setup_s, "unit": units["setup_s"]}
        for name, v in out["metrics"].items():
            metrics[name] = {"value": float(v), "unit": units[name]}

    c0 = ctx.notes.get("compiles_setup", {})
    c1 = ctx.notes.get("compiles_end", {})
    diag = {"workload": args.workload, "seed": args.seed,
            "setup_s": setup_s, "setup_parts": ctx.setup_parts,
            "compiles_in_setup": c0,
            "compiles_in_window": {k: c1.get(k, 0) - c0.get(k, 0)
                                   for k in c0},
            "memory_peak_bytes": out["device"]["memory_peak_bytes"],
            "metrics": metrics,
            "notes": {k: v for k, v in ctx.notes.items()
                      if k != "trace_hooks"}}
    text = json.dumps(diag, default=str)
    for k in ("setup_parts", "compiles_in_setup", "compiles_in_window"):
        log(f"{k}: {json.dumps(diag[k], default=str)}")
    for k in ("gc_frozen", "gc_in_window", "latency_ms",
              "generator_late_ms_max"):
        if k in ctx.notes:
            log(f"{k}: {json.dumps(ctx.notes[k], default=str)}")
    log(f"diagnostics: {text[:6000]}")
    (HERE / ".out").mkdir(exist_ok=True)
    (HERE / ".out" / f"{args.workload}-{args.seed}-t{args.trace}.json") \
        .write_text(text)

    checks = out["checks"]
    correct = all(c.ok for c in checks)
    for c in checks:
        log(f"check {c.name} = {c.value!r} limit {c.limit!r} "
            f"{'ok' if c.ok else 'FAIL'}")
    line = {"correct": correct, "attempted": int(out["attempted"]),
            "failed": int(out["failed"]), "metrics": metrics,
            "device": result_device}
    if breakdown is not None:
        line["breakdown"] = breakdown
    line["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                      for c in checks}
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
if os.environ.get("REPRO_DRYRUN_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        + os.environ["REPRO_DRYRUN_DEVICES"])

"""Per-instruction HBM/flop attribution for one dry-run cell — the §Perf
"profiler" (we have no wall-clock on CPU; the lowered module is the profile).

    python -m repro.launch.profile_cell --arch minicpm-2b --shape train_4k \
        [--gs gs-richtmyer-meshkov] [--top 20] [--by flops]

``--gs-train DATASET`` profiles the PRODUCTION trainer instead of the
dense dry-run cell: the tiered ``make_gs_train_step`` that
``fit_partitions`` (and the ``--timeseries`` loop, once per timestep)
dispatches, lowered on the real ("part", "view") mesh — so per-timestep
profiles attribute the step the devices actually run:

    REPRO_DRYRUN_DEVICES=4 python -m repro.launch.profile_cell \
        --gs-train sphere_shell --gs-res 32 --top 10
"""

import argparse
import math
import re
from collections import Counter

import jax

from repro.launch import hlo_analysis as H
from repro.launch.dryrun import (lower_gs_cell, lower_gs_train_cell,
                                 lower_lm_cell, make_meshes)
from repro.configs import get_spec
from repro.launch.mesh import make_mesh

OPNAME_RE = re.compile(r'op_name="([^"]*)"')


def attribute(mod: H.HloModule, by: str = "hbm"):
    contrib = Counter()

    def walk(comp, mult, top):
        for inst in mod.insts[comp]:
            c = H.HloCosts()
            mod._inst_costs(inst, c, top)
            val = c.hbm_bytes if by == "hbm" else c.flops
            if inst.opcode == "fusion":
                m = H.CALLS_RE.search(inst.line)
                if m:
                    sub = mod._comp_costs(m.group(1), False)
                    if by == "flops":
                        val += sub.flops
                    elif top:
                        r, w = mod._fusion_io_bytes(
                            m.group(1), inst.operands,
                            mod._sym(inst.name).bytes)
                        val += r + w
            if val:
                om = OPNAME_RE.search(inst.line)
                tag = om.group(1) if om else inst.opcode
                # collapse jit/transpose noise to the semantic op
                tag = re.sub(r"jit\(\w+\)/", "", tag)
                contrib[(inst.opcode, tag[:95])] += val * mult
            if inst.opcode == "while":
                bm = H.BODY_RE.search(inst.line)
                tm = H.TRIP_RE.search(inst.line)
                if bm:
                    walk(bm.group(1), mult * (int(tm.group(1)) if tm else 1),
                         top)

    walk(mod.entry, 1, True)
    return contrib


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="minicpm-2b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--gs", default="")
    ap.add_argument("--gs-train", default="",
                    help="profile the production tiered GS train step for "
                         "this dataset (sphere_shell/kingsnake/...) on a "
                         "('part','view') mesh")
    ap.add_argument("--gs-res", type=int, default=64)
    ap.add_argument("--gs-parts", type=int, default=2)
    ap.add_argument("--gs-view-batch", type=int, default=2)
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--top", type=int, default=20)
    ap.add_argument("--by", default="hbm", choices=["hbm", "flops"])
    args = ap.parse_args()

    if args.gs_train:
        n = len(jax.devices())
        v = math.gcd(max(1, args.gs_view_batch), n)
        mesh = make_mesh((n // v, v), ("part", "view"))
        lowered, meta = lower_gs_train_cell(
            args.gs_train, mesh, res=args.gs_res, n_parts=args.gs_parts,
            view_batch=args.gs_view_batch)
        name = (f"gs-train-{args.gs_train} res={meta['resolution']} "
                f"parts={meta['n_parts']} N/part="
                f"{meta['gaussians_per_part']} k_tiers={meta['k_tiers']}")
        args.mesh = f"{n // v}x{v} part,view"
    elif args.gs:
        mesh = make_meshes(args.mesh)[args.mesh]
        lowered, _, _ = lower_gs_cell(args.gs, mesh)
        name = args.gs
    else:
        mesh = make_meshes(args.mesh)[args.mesh]
        lowered = lower_lm_cell(get_spec(args.arch), args.shape, mesh)
        name = f"{args.arch}__{args.shape}"
    txt = lowered.compile().as_text()
    pod = 0
    mod = H.HloModule(txt, pod_size=pod)
    contrib = attribute(mod, args.by)
    total = sum(contrib.values())
    unit = "GB" if args.by == "hbm" else "GFLOP"
    print(f"{name} [{args.mesh}]  total {total/1e9:.1f} {unit} per device")
    for (opcode, tag), v in contrib.most_common(args.top):
        print(f"{v/1e9:10.2f} {unit}  {100*v/total:5.1f}%  {opcode:18s} {tag}")


if __name__ == "__main__":
    main()

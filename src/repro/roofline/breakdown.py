"""Per-instruction breakdown of a dry-run cell — the 'profile' for the
hypothesis->change->measure loop (no real hardware; the lowered IR is the
profiler).

    PYTHONPATH=src python -m repro.roofline.breakdown --arch deepseek-moe-16b \
        --shape train_4k [--mesh single] [--top 20] [--rule expert=]

Prints the top collectives and top HBM-traffic instructions with their
loop multipliers and source op_names (metadata) so changes can be traced
back to model code.
"""
from __future__ import annotations

import os

if __name__ == "__main__" and "--no-devices" not in os.sys.argv:
    # Parity with dryrun: 512 host devices, for the CLI only, so that
    # importing this module never changes a caller's device count.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse  # noqa: E402
import re        # noqa: E402

from repro.roofline.hlo_analysis import (_SKIP_BYTES, _called,  # noqa: E402
                                         _instr_traffic, _parse_computations,
                                         _trip_count, _virtual_set, _dot_flops)


def collect_rows(hlo_text: str):
    comps, entry = _parse_computations(hlo_text)
    coll_rows, hbm_rows, flop_rows = [], [], []

    def metadata(ins):
        m = re.search(r'op_name="([^"]+)"', ins.attrs)
        return m.group(1)[-90:] if m else ""

    def walk(cname, mult, seen):
        comp = comps.get(cname)
        if comp is None or cname in seen:
            return
        virtual = _virtual_set(comp)
        rm: dict = {}
        for iname in comp.order:
            ins = comp.instructions[iname]
            m2 = mult * ((_trip_count(ins) or 1.0)
                         if ins.opcode == "while" else 1.0)
            for sub in _called(ins):
                walk(sub, m2, seen)
            base = ins.opcode.replace("-start", "")
            if base in ("all-gather", "all-reduce", "reduce-scatter",
                        "all-to-all", "collective-permute"):
                ob = sum(comp.instructions[o].bytes for o in ins.operands
                         if o in comp.instructions)
                coll_rows.append((mult * ob, mult, base, ins.type_str[:40],
                                  metadata(ins)))
            if ins.opcode == "dot":
                flop_rows.append((mult * _dot_flops(ins, comp), mult,
                                  ins.type_str[:40], metadata(ins)))
            if ins.opcode not in _SKIP_BYTES and iname not in virtual:
                b = _instr_traffic(ins, comp, virtual, rm, comps)
                hbm_rows.append((mult * b, mult, ins.opcode,
                                 ins.type_str[:40], metadata(ins)))

    walk(entry, 1.0, set())
    return coll_rows, hbm_rows, flop_rows


def print_top(rows, title, top, unit=1e9, suffix="GB"):
    print(f"\n== top {title} ==")
    for row in sorted(rows, reverse=True)[:top]:
        val, mult, *rest = row
        print(f"{val / unit:12.2f} {suffix} x{mult:6.0f}  " +
              "  ".join(str(r) for r in rest))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True)
    ap.add_argument("--mesh", default="single")
    ap.add_argument("--top", type=int, default=15)
    ap.add_argument("--rule", action="append", default=[])
    ap.add_argument("--accum", type=int, default=None)
    ap.add_argument("--remat", default="full")
    args = ap.parse_args()

    from repro.launch import dryrun as dr
    overrides = {}
    for r in args.rule:
        k, _, v = r.partition("=")
        overrides[k] = tuple(x for x in v.split(",") if x) or None

    # reuse the dryrun lowering, but grab the compiled text
    import jax
    from repro.configs import get_config
    from repro.configs.registry import get_shape
    from repro.launch.partition import param_sharding, partitioning
    from repro.launch.specs import batch_specs, sharding_for_axes
    from repro.optim import cosine_schedule, pick_optimizer
    from repro.train import train_step as ts

    cfg = get_config(args.arch)
    shape = get_shape(args.shape)
    mesh = dr._mesh_for(args.mesh)
    rules = dr._rules_for(mesh, shape.global_batch, overrides or None, cfg)
    specs, axes = batch_specs(cfg, shape)
    in_sh = sharding_for_axes(mesh, axes, rules)
    with partitioning(mesh, rules):
        if shape.kind == "train":
            accum = args.accum or dr._auto_accum(cfg, shape, mesh, rules)
            opt = pick_optimizer(cfg.total_params(), cosine_schedule(3e-4))
            state_abs = ts.abstract_state(cfg, opt)
            state_sh = param_sharding(ts.state_logical_axes(cfg, opt), mesh,
                                      rules, state_abs)
            step = ts.make_train_step(cfg, opt, remat=args.remat,
                                      accum_steps=accum,
                                      grad_shardings=state_sh.params)
            compiled = jax.jit(step, in_shardings=(state_sh, in_sh),
                               out_shardings=(state_sh, None),
                               donate_argnums=(0,)).lower(state_abs,
                                                          specs).compile()
            print(f"accum={accum}")
        else:
            raise SystemExit("breakdown currently supports train shapes")
    coll, hbm, flops = collect_rows(compiled.as_text())
    print_top(coll, "collectives", args.top)
    print_top(hbm, "HBM traffic", args.top)
    print_top(flops, "dot FLOPs", args.top, unit=1e12, suffix="TF")


if __name__ == "__main__":
    main()

import os
if __name__ == "__main__":
    # Placeholder 512-device fleet for the dry-run CLI only. Guarded so that
    # *importing* this module (test_partition_rules, breakdown) never forces
    # the flag onto an in-process suite — conftest.py promises smoke tests
    # and benchmarks see the real device count.
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# NOTE: when run as the CLI, the lines above MUST execute before any
# jax-importing module — jax locks the device count at first init.
import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import pathlib           # noqa: E402
import subprocess        # noqa: E402
import sys               # noqa: E402
import time              # noqa: E402
import traceback         # noqa: E402

import jax               # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import SHAPES, get_config  # noqa: E402
from repro.configs.registry import cells, get_shape, list_archs, runnable_cell  # noqa: E402
from repro.launch.mesh import batch_axes_for, make_production_mesh  # noqa: E402
from repro.launch.partition import DEFAULT_RULES, param_sharding, partitioning  # noqa: E402
from repro.launch.specs import batch_specs, sharding_for_axes  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.optim import cosine_schedule, pick_optimizer  # noqa: E402
from repro.roofline import analyze_hlo  # noqa: E402
from repro.roofline.report import V5E, model_flops, roofline_terms  # noqa: E402
from repro.train import train_step as ts  # noqa: E402

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / "results" / "dryrun"

# The paper's own workload, as first-class dry-run cells (DESIGN.md §4).
ICP_SHAPES = {
    # fleet: one KITTI-like frame-pair per vehicle, paper-sized clouds
    "fleet_130k": dict(frames=256, n_src=4096, m_dst=131072, iters=50),
    # giant-frame: scan-to-city-map registration, target over every chip
    "giant_134m": dict(frames=1, n_src=65536, m_dst=2 ** 27, iters=50),
}


def _mesh_for(name: str):
    return make_production_mesh(multi_pod=(name == "multi"))


def _trim_batch_axes(mesh, axes, global_batch: int):
    """Longest prefix of ``axes`` (present in mesh) dividing global_batch."""
    chosen, size = [], 1
    for ax in axes or ():
        if ax not in mesh.axis_names:
            continue
        if global_batch % (size * mesh.shape[ax]) == 0:
            chosen.append(ax)
            size *= mesh.shape[ax]
        else:
            break
    return tuple(chosen)


def _rules_for(mesh, global_batch: int, overrides: dict | None = None,
               cfg=None):
    rules = dict(DEFAULT_RULES)
    rules["batch"] = batch_axes_for(mesh, global_batch)
    if cfg is not None:
        for k, v in cfg.sharding_override_rules.items():
            if k == "batch":
                rules[k] = _trim_batch_axes(mesh, v, global_batch)
            else:
                rules[k] = v
    rules["tokens"] = rules["batch"]  # flattened (B*S) dim follows batch
    if overrides:
        rules.update(overrides)
    return rules


def _collect(compiled, label: str, n_devices: int, cfg=None, shape=None,
             model_flops_override=None) -> dict:
    mem = compiled.memory_analysis()
    naive = compiled.cost_analysis()
    hlo = compiled.as_text()
    cost = analyze_hlo(hlo)
    terms = roofline_terms(cost, cfg, shape, n_devices,
                           model_flops_override=model_flops_override)
    out = {
        "label": label,
        "n_devices": n_devices,
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
            "alias_bytes": mem.alias_size_in_bytes,
            "fits_v5e_16g": (mem.argument_size_in_bytes
                             + mem.temp_size_in_bytes) < V5E["hbm_bytes"],
        },
        "naive_cost_analysis": {
            "flops": naive.get("flops"),
            "bytes_accessed": naive.get("bytes accessed"),
        },
        "analyzed": cost.to_json(),
        "roofline": terms.to_json(),
    }
    return out


def _auto_accum(cfg, shape, mesh, rules) -> int:
    """Gradient-accumulation depth: keep per-device microbatch tokens small
    enough that checkpointed activations fit HBM (width-dependent)."""
    axes = rules.get("batch") or ()
    shards = 1
    for a in axes:
        shards *= mesh.shape[a]
    b_loc = max(1, shape.global_batch // max(shards, 1))
    tokens_loc = b_loc * shape.seq_len
    if cfg.d_model >= 12288:
        target = 4096
    elif cfg.d_model >= 4096:
        target = 8192
    else:
        target = 16384
    accum = max(1, tokens_loc // target)
    while b_loc % accum:  # accum must divide the local batch
        accum -= 1
    return accum


def _lower_lm_cell(arch: str, shape_name: str, mesh_name: str,
                   rules_overrides: dict | None = None,
                   remat: str = "full", accum: int | None = None,
                   kv_quant: bool = False) -> dict:
    cfg = get_config(arch)
    if kv_quant:
        cfg = dataclasses.replace(cfg, kv_quant=True)
    shape = get_shape(shape_name)
    mesh = _mesh_for(mesh_name)
    n_dev = mesh.devices.size
    rules = _rules_for(mesh, shape.global_batch, rules_overrides, cfg)
    specs, axes = batch_specs(cfg, shape)
    in_sh = sharding_for_axes(mesh, axes, rules)

    t0 = time.time()
    with partitioning(mesh, rules):
        if shape.kind == "train":
            if accum is None:
                accum = _auto_accum(cfg, shape, mesh, rules)
            opt = pick_optimizer(cfg.total_params(), cosine_schedule(3e-4))
            state_abs = ts.abstract_state(cfg, opt)
            state_axes = ts.state_logical_axes(cfg, opt)
            state_sh = param_sharding(state_axes, mesh, rules, state_abs)
            step = ts.make_train_step(cfg, opt, remat=remat,
                                      accum_steps=accum,
                                      grad_shardings=state_sh.params)
            jf = jax.jit(step, in_shardings=(state_sh, in_sh),
                         out_shardings=(state_sh, None),
                         donate_argnums=(0,))
            lowered = jf.lower(state_abs, specs)
        elif shape.kind == "prefill":
            params_abs = lm.init_abstract(cfg)
            p_axes = lm.param_logical_axes(params_abs)
            p_sh = param_sharding(p_axes, mesh, rules, params_abs)

            def prefill_fn(params, inputs):
                return lm.prefill(params, cfg, max_len=shape.seq_len,
                                  remat=remat, **inputs)

            jf = jax.jit(prefill_fn, in_shardings=(p_sh, in_sh))
            lowered = jf.lower(params_abs, specs)
        else:  # decode
            params_abs = lm.init_abstract(cfg)
            p_axes = lm.param_logical_axes(params_abs)
            p_sh = param_sharding(p_axes, mesh, rules, params_abs)
            cache_abs = jax.eval_shape(
                lambda: lm.init_cache(cfg, shape.global_batch, shape.seq_len))
            c_axes = lm.cache_logical_axes(cache_abs)
            c_sh = param_sharding(c_axes, mesh, rules, cache_abs)

            def serve_step(params, cache, inputs):
                pos = inputs["pos"]
                kw = ({"token": inputs["token"]} if cfg.embed_inputs
                      else {"embed": inputs["embed"]})
                logits, new_cache = lm.decode_step(params, cfg, pos, cache,
                                                   **kw)
                return logits, new_cache

            jf = jax.jit(serve_step, in_shardings=(p_sh, c_sh, in_sh),
                         out_shardings=(None, c_sh), donate_argnums=(1,))
            lowered = jf.lower(params_abs, cache_abs, specs)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    out = _collect(compiled, f"{arch}/{shape_name}/{mesh_name}", n_dev,
                   cfg=cfg, shape=shape)
    out["timing"] = {"lower_s": t_lower, "compile_s": t_compile}
    out["remat"] = remat
    out["rules"] = {k: list(v) if isinstance(v, tuple) else v
                    for k, v in rules.items()}
    print(compiled.memory_analysis())
    ca = compiled.cost_analysis()
    print({k: ca[k] for k in ("flops", "bytes accessed") if k in ca})
    return out


def _lower_icp_cell(shape_name: str, mesh_name: str,
                    score_dtype: str = "fp32") -> dict:
    from repro.core.distributed import batched_icp_sharded
    from repro.core.icp import ICPParams

    spec = ICP_SHAPES[shape_name]
    mesh = _mesh_for(mesh_name)
    n_dev = mesh.devices.size
    f, n, m = spec["frames"], spec["n_src"], spec["m_dst"]
    frame_axes = batch_axes_for(mesh, f)
    # giant frame: spread the target over every remaining axis too
    target_axes = tuple(ax for ax in ("data", "model")
                        if ax not in frame_axes or f == 1)
    if f == 1:
        frame_axes = ()
        target_axes = tuple(mesh.axis_names)
    params = ICPParams(max_iterations=spec["iters"], chunk=2048,
                       score_dtype=score_dtype)

    def step(src_b, dst_b):
        return batched_icp_sharded(mesh, src_b, dst_b, params,
                                   frame_axes=frame_axes,
                                   target_axes=target_axes,
                                   fixed_iterations=True)

    src_abs = jax.ShapeDtypeStruct((f, n, 3), jnp.float32)
    dst_abs = jax.ShapeDtypeStruct((f, m, 3), jnp.float32)
    in_sh = (NamedSharding(mesh, P(frame_axes or None)),
             NamedSharding(mesh, P(frame_axes or None, target_axes)))
    t0 = time.time()
    with mesh:
        jf = jax.jit(step, in_shardings=in_sh)
        lowered = jf.lower(src_abs, dst_abs)
        t_lower = time.time() - t0
        t0 = time.time()
        compiled = lowered.compile()
        t_compile = time.time() - t0
    # useful flops: the xyz distance cross-term (2*3*N*M per iteration) —
    # augmentation/argmin overheads count against the engine, not the task
    useful = spec["iters"] * f * (2.0 * 3 * n * m) / n_dev
    out = _collect(compiled, f"fpps-icp/{shape_name}/{mesh_name}", n_dev,
                   model_flops_override=useful)
    out["timing"] = {"lower_s": t_lower, "compile_s": t_compile}
    out["icp_spec"] = spec
    out["sharding"] = {"frame_axes": list(frame_axes),
                       "target_axes": list(target_axes)}
    print(compiled.memory_analysis())
    return out


def run_cell(arch: str, shape_name: str, mesh_name: str,
             out_dir: pathlib.Path, remat: str = "full",
             rules_overrides: dict | None = None,
             accum: int | None = None,
             icp_score_dtype: str = "fp32",
             kv_quant: bool = False) -> dict:
    out_dir.mkdir(parents=True, exist_ok=True)
    path = out_dir / f"{arch}__{shape_name}__{mesh_name}.json"
    try:
        if arch == "fpps-icp":
            rec = _lower_icp_cell(shape_name, mesh_name,
                                  score_dtype=icp_score_dtype)
        else:
            ok, reason = runnable_cell(arch, shape_name)
            if not ok:
                rec = {"label": f"{arch}/{shape_name}/{mesh_name}",
                       "skipped": True, "reason": reason}
                path.write_text(json.dumps(rec, indent=2))
                print(f"SKIP {rec['label']}: {reason}")
                return rec
            rec = _lower_lm_cell(arch, shape_name, mesh_name,
                                 rules_overrides, remat, accum=accum,
                                 kv_quant=kv_quant)
        rec["status"] = "ok"
    except Exception as e:  # record failures as artifacts, don't hide them
        rec = {"label": f"{arch}/{shape_name}/{mesh_name}", "status": "error",
               "error": f"{type(e).__name__}: {e}",
               "traceback": traceback.format_exc()[-4000:]}
    path.write_text(json.dumps(rec, indent=2, default=str))
    status = rec.get("status")
    print(f"[{status}] {rec['label']} -> {path}")
    if status == "ok" and "roofline" in rec:
        r = rec["roofline"]
        print(f"  compute={r['compute_s']:.4f}s memory={r['memory_s']:.4f}s "
              f"collective={r['collective_s']:.4f}s dominant={r['dominant']} "
              f"useful_frac={r['useful_fraction']:.3f}")
    return rec


def run_all(out_dir: pathlib.Path, meshes=("single", "multi"),
            only_missing: bool = True, timeout_s: int = 3600):
    """Spawn one subprocess per cell — isolates compile memory and keeps a
    single bad cell from killing the sweep."""
    all_cells = [(a, s) for (a, s) in cells()]
    all_cells += [("fpps-icp", s) for s in ICP_SHAPES]
    results = []
    for mesh_name in meshes:
        for arch, shape in all_cells:
            path = out_dir / f"{arch}__{shape}__{mesh_name}.json"
            if only_missing and path.exists():
                rec = json.loads(path.read_text())
                if rec.get("status") == "ok" or rec.get("skipped"):
                    continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--shape", shape, "--mesh", mesh_name,
                   "--out-dir", str(out_dir)]
            print("==>", " ".join(cmd), flush=True)
            t0 = time.time()
            # The cell models forced host devices: keep it off the chip.
            proc = subprocess.run(cmd, timeout=timeout_s,
                                  capture_output=True, text=True,
                                  env={**os.environ, "JAX_PLATFORMS": "cpu"})
            dt = time.time() - t0
            if proc.returncode != 0:
                err = {"label": f"{arch}/{shape}/{mesh_name}",
                       "status": "error",
                       "error": f"subprocess rc={proc.returncode}",
                       "stderr": proc.stderr[-4000:]}
                path.write_text(json.dumps(err, indent=2))
                print(f"[error rc={proc.returncode} {dt:.0f}s] "
                      f"{arch}/{shape}/{mesh_name}", flush=True)
            else:
                print(f"[done {dt:.0f}s] {arch}/{shape}/{mesh_name}",
                      flush=True)
            results.append(path)
    return results


def main():
    ap = argparse.ArgumentParser(description="FPPS multi-pod dry-run")
    ap.add_argument("--arch", choices=list_archs() + ["fpps-icp"])
    ap.add_argument("--shape")
    ap.add_argument("--mesh", choices=["single", "multi"], default="single")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true",
                    help="with --all: re-run cells that already have results")
    ap.add_argument("--remat", default="full",
                    choices=["none", "full", "dots"])
    ap.add_argument("--accum", type=int, default=None,
                    help="gradient-accumulation depth (default: auto)")
    ap.add_argument("--icp-score-dtype", default="fp32",
                    choices=["fp32", "bf16"])
    ap.add_argument("--kv-quant", action="store_true",
                    help="int8 KV cache for decode cells")
    ap.add_argument("--rule", action="append", default=[],
                    help="logical-axis rule override, e.g. seq=data or "
                         "expert=; repeatable")
    ap.add_argument("--out-dir", default=str(RESULTS_DIR))
    args = ap.parse_args()

    out_dir = pathlib.Path(args.out_dir)
    if args.all:
        run_all(out_dir, only_missing=not args.force)
        return
    if not args.arch or not args.shape:
        ap.error("--arch and --shape required (or --all)")
    overrides = {}
    for r in args.rule:
        k, _, v = r.partition("=")
        overrides[k] = tuple(x for x in v.split(",") if x) or None
    run_cell(args.arch, args.shape, args.mesh, out_dir,
             remat=args.remat, rules_overrides=overrides or None,
             accum=args.accum, icp_score_dtype=args.icp_score_dtype,
             kv_quant=args.kv_quant)


if __name__ == "__main__":
    main()

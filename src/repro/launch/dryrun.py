import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The lines above MUST run before any other import: jax locks the platform and
# device count at first initialization. The dry-run (and only the dry-run)
# builds the 512-way production meshes on CPU stand-in devices, and never
# takes a chip.
"""Multi-pod dry-run driver.

For every assigned (architecture x input shape) cell and each production mesh
(single-pod 16x16, multi-pod 2x16x16), lower + compile the corresponding step
function against ShapeDtypeStruct inputs (no allocation), then record:
  - compiled.memory_analysis()  (per-device bytes: proves the cell fits)
  - compiled.cost_analysis()    (XLA's own numbers, for reference)
  - the trip-count-aware HLO analysis (FLOPs / HBM bytes / collective bytes)
  - the three roofline terms (single-pod table feeds EXPERIMENTS.md §Roofline)

Usage:
  python -m repro.launch.dryrun --arch tinyllama-1.1b --shape train_4k --mesh both
  python -m repro.launch.dryrun --all --out results/dryrun.json
"""
import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp

from repro.analysis.hlo import analyze_hlo
from repro.analysis.roofline import model_flops, roofline_terms
from repro.configs import SHAPES, cache_specs, get_arch, input_specs
from repro.configs.registry import ARCHS
from repro.distributed.sharding import (
    TRAIN_RULES,
    batch_spec,
    plan_tree,
)
from repro.distributed.sharding import SERVE_RULES
from repro.launch.mesh import make_production_mesh
from repro.models.api import build_model
from repro.models.common import activation_sharding
from repro.serve.engine import serve_shardings
from repro.train.optimizer import OptimizerConfig, abstract_opt_state
from repro.train.step import build_train_step


def _batch_shardings(mesh, specs: dict):
    return {k: batch_spec(mesh, v.ndim, v.shape[0]) for k, v in specs.items()}


def lower_cell(arch_id: str, shape_name: str, mesh, mesh_name: str,
               *, remat: str = "full", extra_cfg: dict | None = None,
               return_text: bool = False):
    """Lower + compile one cell; returns a result dict (or raises)."""
    arch = get_arch(arch_id)
    shape = SHAPES[shape_name]
    cfg = arch.config
    if shape.kind == "train" and remat != cfg.remat_policy:
        cfg = dataclasses.replace(cfg, remat_policy=remat)
    if extra_cfg:
        cfg = dataclasses.replace(cfg, **extra_cfg)
    model = build_model(cfg)
    params_abs, axes = model.init(None)  # abstract init: no allocation

    t0 = time.time()
    chips = mesh.devices.size
    batch_abs = input_specs(cfg, shape)
    b_sh = _batch_shardings(mesh, batch_abs)

    if shape.kind == "train":
        p_sh = plan_tree(mesh, params_abs, axes, TRAIN_RULES)
        opt_abs = abstract_opt_state(params_abs)
        o_sh = {
            "master": p_sh, "m": p_sh, "v": p_sh,
            "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec()),
        }
        step = build_train_step(model, OptimizerConfig())
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        metrics_sh = {k: rep for k in ("grad_norm", "lr", "param_norm", "loss")}
        jitted = jax.jit(
            step,
            in_shardings=(p_sh, o_sh, b_sh),
            out_shardings=(p_sh, o_sh, metrics_sh),
            donate_argnums=(0, 1),
        )
        with activation_sharding(mesh, TRAIN_RULES):
            lowered = jitted.lower(params_abs, opt_abs, batch_abs)
    else:
        cache_abs = cache_specs(cfg, shape)
        p_sh, c_sh = serve_shardings(mesh, model, params_abs, axes, cache_abs)
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        logit_sh = batch_spec(mesh, 3, shape.global_batch)
        with activation_sharding(mesh, SERVE_RULES):
            if shape.kind == "prefill":
                fn = lambda p, c, b: model.prefill(p, c, b)
                jitted = jax.jit(fn, in_shardings=(p_sh, c_sh, b_sh),
                                 out_shardings=(logit_sh, c_sh), donate_argnums=(1,))
                lowered = jitted.lower(params_abs, cache_abs, batch_abs)
            else:
                fn = lambda p, c, t: model.decode_step(p, c, t)
                jitted = jax.jit(fn, in_shardings=(p_sh, c_sh, b_sh["tokens"]),
                                 out_shardings=(logit_sh, c_sh), donate_argnums=(1,))
                lowered = jitted.lower(params_abs, cache_abs, batch_abs["tokens"])
    t_lower = time.time() - t0

    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    text = compiled.as_text()
    hlo = analyze_hlo(text, total_devices=chips)
    terms = roofline_terms(arch_id, shape_name, mesh_name, chips, hlo,
                           model_flops(cfg, shape))
    return ({"hlo_text": text} if return_text else {}) | {
        "arch": arch_id, "shape": shape_name, "mesh": mesh_name, "chips": chips,
        "status": "ok",
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "peak_estimate_bytes": mem.argument_size_in_bytes
            + mem.temp_size_in_bytes + mem.output_size_in_bytes
            - mem.alias_size_in_bytes,
        },
        "xla_cost": {k: cost.get(k) for k in ("flops", "bytes accessed")},
        "roofline": terms.row(),
    }


def run_matrix(arch_ids, shape_names, meshes, *, out_path=None, remat="full"):
    results = []
    mesh_objs = {}
    for mname in meshes:
        mesh_objs[mname] = make_production_mesh(multi_pod=(mname == "multi"))
    for arch_id in arch_ids:
        arch = get_arch(arch_id)
        for shape_name in shape_names:
            ok, reason = arch.supports(SHAPES[shape_name])
            if not ok:
                results.append({"arch": arch_id, "shape": shape_name,
                                "status": "skip", "reason": reason})
                print(f"[skip] {arch_id} x {shape_name}: {reason}")
                continue
            for mname, mesh in mesh_objs.items():
                tag = f"{arch_id} x {shape_name} x {mname}"
                try:
                    r = lower_cell(arch_id, shape_name, mesh, mname, remat=remat)
                    results.append(r)
                    rf = r["roofline"]
                    print(f"[ok]   {tag}: compile={r['compile_s']}s "
                          f"peak={r['memory']['peak_estimate_bytes']/2**30:.2f}GiB/dev "
                          f"dom={rf['dominant']} "
                          f"terms=({rf['compute_s']:.4f},{rf['memory_s']:.4f},"
                          f"{rf['collective_s']:.4f})s "
                          f"roofline_frac={rf['roofline_fraction']:.3f}")
                except Exception as e:  # a failure here is a bug in the system
                    results.append({"arch": arch_id, "shape": shape_name,
                                    "mesh": mname, "status": "fail",
                                    "error": f"{type(e).__name__}: {e}"})
                    print(f"[FAIL] {tag}: {type(e).__name__}: {e}")
                    traceback.print_exc()
                if out_path:
                    with open(out_path, "w") as fh:
                        json.dump(results, fh, indent=1, default=str)
    return results


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", action="append", default=None)
    ap.add_argument("--shape", action="append", default=None)
    ap.add_argument("--mesh", choices=["single", "multi", "both"], default="both")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    arch_ids = list(ARCHS) if (args.all or not args.arch) else args.arch
    shape_names = list(SHAPES) if (args.all or not args.shape) else args.shape
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    results = run_matrix(arch_ids, shape_names, meshes,
                         out_path=args.out, remat=args.remat)
    n_ok = sum(1 for r in results if r.get("status") == "ok")
    n_fail = sum(1 for r in results if r.get("status") == "fail")
    n_skip = sum(1 for r in results if r.get("status") == "skip")
    print(f"\n=== dry-run matrix: {n_ok} ok, {n_fail} FAIL, {n_skip} skip ===")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())

"""Circuit-planner benchmark: Algorithm 1 vs baselines on the *real*
collective traffic of compiled training steps (the paper's technique applied
to the framework's own communication).

Compiles one MoE and one dense train cell on the multi-pod mesh (in a
subprocess with 512 stand-in devices), extracts the cross-block coflows, and
schedules them on the OCS pod-interconnect fabric.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys
import textwrap

SCRIPT = """
import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
import json, dataclasses, jax
from repro.launch.mesh import make_production_mesh
from repro.configs import SHAPES, get_arch, input_specs
from repro.models.api import build_model
from repro.models.common import activation_sharding
from repro.distributed.sharding import TRAIN_RULES, plan_tree, batch_spec
from repro.train.optimizer import OptimizerConfig, abstract_opt_state
from repro.train.step import build_train_step
from repro.analysis.hlo import analyze_hlo
from repro.comm import BlockMap, step_coflows, plan_circuits, OCSFabric

mesh = make_production_mesh(multi_pod=True)
out = {}
for arch_id in %(archs)s:
    arch = get_arch(arch_id)
    cfg = dataclasses.replace(arch.config, remat_policy="full")
    model = build_model(cfg)
    params, axes = model.init(None)
    shape = SHAPES["train_4k"]
    batch = input_specs(cfg, shape)
    p_sh = plan_tree(mesh, params, axes, TRAIN_RULES)
    o_sh = {"master": p_sh, "m": p_sh, "v": p_sh,
            "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())}
    b_sh = {k: batch_spec(mesh, v.ndim, v.shape[0]) for k, v in batch.items()}
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    msh = {k: rep for k in ("grad_norm", "lr", "param_norm", "loss")}
    step = build_train_step(model, OptimizerConfig())
    with activation_sharding(mesh, TRAIN_RULES):
        comp = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                       out_shardings=(p_sh, o_sh, msh),
                       donate_argnums=(0, 1)).lower(
            params, abstract_opt_state(params), batch).compile()
    an = analyze_hlo(comp.as_text(), total_devices=512)
    bmap = BlockMap.from_mesh_shape(dict(mesh.shape), ("pod", "data"))
    cfs = step_coflows(an, bmap)
    reports = plan_circuits(cfs, OCSFabric())
    out[arch_id] = {
        "collectives": an.collective_counts(),
        "n_coflows": len(cfs),
        "inter_block_GB": sum(c.total_bytes for c in cfs) / 1e9,
        "per_alg": {a: r.row() for a, r in reports.items()},
    }
print("JSON::" + json.dumps(out))
"""


def main(archs=("phi3.5-moe-42b-a6.6b", "tinyllama-1.1b"),
         out_path="results/comm_planner.json") -> dict:
    code = SCRIPT % {"archs": repr(list(archs))}
    # compile-only on stand-in devices: the child must never take the chip
    env = dict(os.environ, PYTHONPATH="src", JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env)
    if res.returncode != 0:
        print(res.stderr[-4000:])
        raise RuntimeError("comm_planner subprocess failed")
    payload = [l for l in res.stdout.splitlines() if l.startswith("JSON::")][-1]
    data = json.loads(payload[len("JSON::"):])
    os.makedirs(os.path.dirname(out_path), exist_ok=True)
    with open(out_path, "w") as fh:
        json.dump(data, fh, indent=1)
    print("== Circuit planner on real step traffic (2-pod mesh, 32 blocks) ==")
    for arch, d in data.items():
        print(f"\n{arch}: {d['n_coflows']} coflows, "
              f"{d['inter_block_GB']:.0f} GB inter-block, "
              f"collectives={d['collectives']}")
        base = d["per_alg"]["ours"]["weighted_cct"]
        for alg, r in d["per_alg"].items():
            print(f"  {alg:14s} wCCT={r['weighted_cct']:9.3f}s "
                  f"makespan={r['makespan']:8.3f}s "
                  f"norm={r['weighted_cct']/base:5.2f}x")
    return data


if __name__ == "__main__":
    main()

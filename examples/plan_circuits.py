"""The paper's technique as a first-class framework feature: compile a
training step, extract its cross-block collective traffic as coflows over
the multi-core OCS pod interconnect, and plan the circuit schedule with
Algorithm 1 — printing the circuit program a Jupiter-style fabric manager
would install.

  PYTHONPATH=src python examples/plan_circuits.py [--arch phi3.5-moe-42b-a6.6b]

Runs on a small stand-in mesh (8 devices) so it finishes in seconds; the
production path (512 devices) is benchmarks/comm_planner.py.
"""
import os

os.environ["JAX_PLATFORMS"] = "cpu"  # stand-in devices: never take the chip
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import argparse

import jax

from repro.analysis.hlo import analyze_hlo
from repro.comm import BlockMap, OCSFabric, plan_circuits, step_coflows
from repro.distributed.sharding import TRAIN_RULES, batch_spec, plan_tree
from repro.launch.mesh import make_mesh
from repro.models.api import ModelConfig, build_model
from repro.models.common import activation_sharding
from repro.train.optimizer import OptimizerConfig, abstract_opt_state
from repro.train.step import build_train_step


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--experts", type=int, default=8)
    args = ap.parse_args()

    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    cfg = ModelConfig(name="demo-moe", family="moe", n_layers=4, d_model=256,
                      n_heads=8, n_kv_heads=4, d_ff=512, vocab=1024,
                      n_experts=args.experts, top_k=2)
    model = build_model(cfg)
    params, axes = model.init(None)
    batch = {"tokens": jax.ShapeDtypeStruct((16, 256), jax.numpy.int32),
             "labels": jax.ShapeDtypeStruct((16, 256), jax.numpy.int32)}
    p_sh = plan_tree(mesh, params, axes, TRAIN_RULES)
    o_sh = {"master": p_sh, "m": p_sh, "v": p_sh,
            "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())}
    b_sh = {k: batch_spec(mesh, v.ndim, v.shape[0]) for k, v in batch.items()}
    rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    msh = {k: rep for k in ("grad_norm", "lr", "param_norm", "loss")}
    step = build_train_step(model, OptimizerConfig())
    with activation_sharding(mesh, TRAIN_RULES):
        compiled = jax.jit(
            step, in_shardings=(p_sh, o_sh, b_sh),
            out_shardings=(p_sh, o_sh, msh)).lower(
            params, abstract_opt_state(params), batch).compile()

    analysis = analyze_hlo(compiled.as_text(), total_devices=8)
    print(f"collectives in the compiled step: {analysis.collective_counts()}")

    bmap = BlockMap.from_mesh_shape(dict(mesh.shape), ("pod", "data"))
    coflows = step_coflows(analysis, bmap)
    print(f"-> {len(coflows)} coflows over {bmap.n_blocks} aggregation blocks, "
          f"{sum(c.total_bytes for c in coflows)/1e6:.1f} MB inter-block")

    fabric = OCSFabric(rates=(25e9, 50e9), delta=1e-3)
    reports = plan_circuits(coflows, fabric)
    base = reports["ours"].weighted_cct
    print(f"\n{'algorithm':14s} {'wCCT':>10s} {'makespan':>10s} {'norm':>6s}")
    for alg, r in reports.items():
        print(f"{alg:14s} {r.weighted_cct:9.4f}s {r.makespan:9.4f}s "
              f"{r.weighted_cct/base:5.2f}x")

    # print the first few circuit establishments of OURS — the program the
    # fabric manager would install
    print("\nfirst 10 circuit establishments (OURS):")
    flows = sorted(reports["ours"].schedule.flows, key=lambda f: f.t_establish)
    for f in flows[:10]:
        print(f"  t={f.t_establish*1e3:7.2f}ms core={f.core} "
              f"block{f.i:2d} -> block{f.j:2d}  "
              f"{f.size/1e6:8.2f} MB  (coflow {f.cid})")


if __name__ == "__main__":
    main()

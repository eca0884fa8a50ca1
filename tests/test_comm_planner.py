"""Collectives-as-coflows planner: extraction from a real compiled step and
Algorithm 1 scheduling with feasibility + theory certificates."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np

from repro.comm import OCSFabric, plan_circuits
from repro.core import check_lemma1, check_theorem1, check_theorem2, validate
from repro.core.coflow import Coflow


def _mk_coflows(seed=0, m=12, n=8):
    rng = np.random.default_rng(seed)
    out = []
    for cid in range(m):
        D = np.zeros((n, n))
        for _ in range(rng.integers(2, 10)):
            D[rng.integers(n), rng.integers(n)] += rng.exponential(1e9)
        out.append(Coflow(cid=cid, demand=D, weight=float(rng.integers(1, 5))))
    return out


def test_plan_circuits_feasible_and_bounded():
    cfs = _mk_coflows()
    reports = plan_circuits(cfs, OCSFabric(rates=(25e9, 50e9), delta=5e-3))
    for alg, r in reports.items():
        validate(r.schedule)  # port exclusivity, timing, conservation
        check_lemma1(r.schedule)
    ours = reports["ours"]
    check_theorem1(ours.schedule)
    check_theorem2(ours.schedule)
    assert ours.weighted_cct > 0
    assert ours.ideal_lb_sum <= ours.total_cct + 1e-9


def test_planner_on_compiled_step():
    """Extract coflows from a real compiled training step (8 fake devices)."""
    code = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import json, jax, jax.numpy as jnp
        from repro.models.api import ModelConfig, build_model
        from repro.train.optimizer import OptimizerConfig, abstract_opt_state
        from repro.train.step import build_train_step
        from repro.distributed.sharding import TRAIN_RULES, plan_tree, batch_spec
        from repro.models.common import activation_sharding
        from repro.analysis.hlo import analyze_hlo
        from repro.comm import BlockMap, step_coflows, plan_circuits
        from repro.launch.mesh import make_mesh

        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        cfg = ModelConfig(name="m", family="moe", n_layers=2, d_model=64,
                          n_heads=4, n_kv_heads=2, d_ff=96, vocab=128,
                          n_experts=4, top_k=2)
        model = build_model(cfg)
        params, axes = model.init(None)
        batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
        p_sh = plan_tree(mesh, params, axes, TRAIN_RULES)
        o_sh = {"master": p_sh, "m": p_sh, "v": p_sh,
                "step": jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())}
        b_sh = {k: batch_spec(mesh, v.ndim, v.shape[0]) for k, v in batch.items()}
        rep = jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
        msh = {k: rep for k in ("grad_norm", "lr", "param_norm", "loss")}
        step = build_train_step(model, OptimizerConfig())
        with activation_sharding(mesh, TRAIN_RULES):
            comp = jax.jit(step, in_shardings=(p_sh, o_sh, b_sh),
                           out_shardings=(p_sh, o_sh, msh)).lower(
                params, abstract_opt_state(params), batch).compile()
        an = analyze_hlo(comp.as_text(), total_devices=8)
        bmap = BlockMap.from_mesh_shape(dict(mesh.shape), ("pod", "data"))
        cfs = step_coflows(an, bmap)
        reports = plan_circuits(cfs)
        print(json.dumps({
            "n_coll": sum(an.collective_counts().values()),
            "n_coflows": len(cfs),
            "bytes": sum(c.total_bytes for c in cfs),
            "ours": reports["ours"].weighted_cct,
            "rand_sunflow": reports["rand-sunflow"].weighted_cct,
        }))
    """)
    env = dict(os.environ, PYTHONPATH="src")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, env=env,
                         cwd=os.path.dirname(os.path.dirname(__file__)))
    assert out.returncode == 0, out.stderr[-3000:]
    r = json.loads(out.stdout.strip().splitlines()[-1])
    assert r["n_coll"] > 0 and r["n_coflows"] > 0 and r["bytes"] > 0
    assert r["ours"] > 0 and r["rand_sunflow"] > 0

"""Smoke run of the Pallas assignment path on one TPU, at FB-trace scale.

  python chip_smoke.py

The deployment is the FB2010 coflow benchmark's cluster and backlog: every
one of its 150 racks a port, the whole hour's 526 coflows (synthesized by
``core.trace.synth_fb_trace``, seed 2026), and the paper's four-core fabric
with imbalanced rates 5/10/20/25 and reconfiguration delay 8 (instance
seed 0). Two phases drive it through the entry points a user calls:

  A. the served one-shot plane: ``FabricManager.schedule_instance(inst,
     backend="pallas")``. The kernel's choices pass the ``assign_ref`` fp32
     gate, the program passes the referee, its weighted CCT is within 2% of
     the numpy schedule, and a second identical call hits the cache;
  B. the sweep API: ``run_batch`` over three instance seeds with
     ``backend="pallas"``, ``materialize="metrics"`` and default workers,
     which must finish in this one process.

It prints what it measured, one item per line, and as its last line one
JSON object ``{"ok": true, "device": {...}}``. Without a TPU it exits
non-zero before doing anything: there is no CPU branch. Any failed check
exits non-zero. The compile cache goes where ``JAX_COMPILATION_CACHE_DIR``
says, or to ``.jax_cache`` in the checkout.
"""
from __future__ import annotations

import dataclasses
import json
import os
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

#: The pallas program's weighted CCT may differ from numpy's by this
#: fraction: the kernel takes flow sizes in fp32, and near-ties then break
#: differently (the precision contract, ``kernels/coflow_assign.py``).
WCCT_TOLERANCE = 0.02


class SmokeFailure(Exception):
    """A check of the smoke run failed."""


@dataclasses.dataclass(frozen=True)
class Deployment:
    n_ports: int = 150
    n_coflows: int = 526
    trace_coflows: int = 526  # the FB2010 hour
    rates: tuple = (5.0, 10.0, 20.0, 25.0)
    delta: float = 8.0
    trace_seed: int = 2026
    instance_seeds: tuple = (0, 1, 2)  # phase A takes the first


def _log(msg: str) -> None:
    print(msg, flush=True)


def _check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def build_instances(dep: Deployment) -> list:
    from repro.core import sample_instance, synth_fb_trace

    trace = synth_fb_trace(dep.trace_coflows, seed=dep.trace_seed)
    return [sample_instance(trace, N=dep.n_ports, M=dep.n_coflows,
                            rates=list(dep.rates), delta=dep.delta, seed=s)
            for s in dep.instance_seeds]


def weighted_cct(program, inst) -> float:
    """Weighted CCT of a circuit program over the instance it serves."""
    import numpy as np

    cids = np.array([c.cid for c in inst.coflows])
    order = np.argsort(cids)
    idx = order[np.searchsorted(cids[order], program.cid)]
    ccts = np.zeros(cids.size)
    np.maximum.at(ccts, idx, program.t_complete)
    return float((inst.weights * ccts).sum())


def phase_kernel(inst) -> dict:
    """Compile and time the assignment kernel alone; gate its choices."""
    import jax
    import jax.numpy as jnp

    from repro.core import extract_flows, order_coflows
    from repro.core.engine import kernel_divergence
    from repro.kernels.coflow_assign import coflow_assign_fwd
    from repro.kernels.ops import interpret_mode
    from repro.obs.clock import now

    flows = extract_flows(inst, order_coflows(inst))
    _pos, _cid, fi, fj, sizes = flows
    interpret = interpret_mode()
    args = (jnp.asarray(fi, jnp.int32), jnp.asarray(fj, jnp.int32),
            jnp.asarray(sizes, jnp.float32),
            jnp.asarray(inst.rates, jnp.float32), float(inst.delta))
    t0 = now()
    compiled = coflow_assign_fwd.lower(*args, n_ports=inst.N,
                                       interpret=interpret).compile()
    compile_s = now() - t0
    mosaic = "tpu_custom_call" in compiled.as_text()
    jax.block_until_ready(compiled(*args))  # warm-up
    t0 = now()
    choices = jax.block_until_ready(compiled(*args))
    kernel_s = now() - t0
    diverged, allowed = kernel_divergence(inst, flows, choices)
    _log(f"flows: {fi.size}")
    _log(f"kernel: {'interpreter' if interpret else 'compiled'}, "
         f"tpu_custom_call in HLO: {mosaic}")
    _log(f"kernel compile s: {compile_s}")
    _log(f"kernel wall s (after warm-up, block_until_ready): {kernel_s}")
    _log(f"kernel/assign_ref divergence: {diverged} of {fi.size} flows "
         f"(allowance {allowed})")
    _check(interpret or mosaic, "the kernel did not compile to Mosaic")
    _check(diverged <= allowed,
           f"kernel/assign_ref divergence {diverged} > allowance {allowed}")
    return dict(flows=int(fi.size), compile_s=compile_s, kernel_s=kernel_s,
                diverged=diverged, allowed=allowed)


def phase_served(inst) -> dict:
    """Phase A: the one-shot plane of ``FabricManager`` on the pallas path."""
    import numpy as np

    from repro.obs.clock import now
    from repro.service import FabricConfig, FabricManager

    mgr = FabricManager(FabricConfig(rates=tuple(inst.rates),
                                     delta=inst.delta, N=inst.N))
    t0 = now()
    program, hit = mgr.schedule_instance(inst, backend="pallas")
    pallas_s = now() - t0
    _check(not hit, "first pallas call hit the cache")
    t0 = now()
    program.validate()
    validate_s = now() - t0
    _log(f"referee (program.validate): passed in {validate_s} s")
    t0 = now()
    reference, _ = mgr.schedule_instance(inst, backend="numpy")
    numpy_s = now() - t0
    w_pallas = weighted_cct(program, inst)
    w_numpy = weighted_cct(reference, inst)
    ratio = w_pallas / w_numpy
    _log(f"weighted CCT pallas {w_pallas} vs numpy {w_numpy}: "
         f"ratio {ratio} (limit {1 + WCCT_TOLERANCE})")
    t0 = now()
    again, hit = mgr.schedule_instance(inst, backend="pallas")
    hit_s = now() - t0
    _log(f"second schedule_instance: hit={hit} in {hit_s} s")
    _log(f"phase A wall s: schedule_instance pallas {pallas_s}, "
         f"numpy {numpy_s}")
    _check(abs(ratio - 1.0) <= WCCT_TOLERANCE,
           f"pallas/numpy weighted CCT ratio {ratio} beyond the contract")
    _check(hit, "second identical schedule_instance missed the cache")
    _check(np.array_equal(again.t_complete, program.t_complete),
           "the cached program differs from the computed one")
    return dict(wcct_ratio=ratio, pallas_s=pallas_s, numpy_s=numpy_s,
                validate_s=validate_s)


def phase_sweep(instances, seeds) -> dict:
    """Phase B: ``run_batch`` on the pallas path, in this one process."""
    import math
    import resource

    from repro.core import run_batch
    from repro.obs.clock import now

    def child_cpu_s() -> float:
        ru = resource.getrusage(resource.RUSAGE_CHILDREN)
        return ru.ru_utime + ru.ru_stime

    before = child_cpu_s()
    t0 = now()
    table = run_batch(instances, ("ours",), seeds=tuple(seeds),
                      pair_seeds=True, check="none", backend="pallas",
                      materialize="metrics")
    wall = now() - t0
    children = child_cpu_s() - before
    for row in table:
        _log(f"sweep seed {row.seed}: {row.n_flows} flows, weighted CCT "
             f"{row.weighted_cct}, wall s {row.wall_s}")
    _log(f"phase B wall s: {wall} for {len(table)} runs in pid "
         f"{os.getpid()}, child-process cpu s {children}")
    _check(len(table) == len(instances), "sweep lost grid points")
    _check(all(math.isfinite(r.weighted_cct) and r.weighted_cct > 0
               for r in table), "sweep produced a non-positive weighted CCT")
    _check(children == 0.0, "run_batch started worker processes")
    return dict(wall_s=wall, rows=len(table))


def run(dep: Deployment) -> dict:
    """Both phases on ``dep``; raises ``SmokeFailure`` on a failed check."""
    from repro.obs.clock import now

    t0 = now()
    instances = build_instances(dep)
    _log(f"deployment: N={dep.n_ports} M={dep.n_coflows} rates={dep.rates} "
         f"delta={dep.delta} trace seed {dep.trace_seed}, instance seeds "
         f"{dep.instance_seeds} (built in {now() - t0} s)")
    out = {"kernel": phase_kernel(instances[0])}
    t0 = now()
    out["served"] = phase_served(instances[0])
    _log(f"phase A total s: {now() - t0}")
    out["sweep"] = phase_sweep(instances, dep.instance_seeds)
    return out


def main() -> int:
    try:
        from repro.launch.compile_cache import enable_compile_cache
        from repro.obs.clock import now
    except ImportError as e:
        print(f"chip_smoke: run it from a checkout of the repository: {e}",
              file=sys.stderr)
        return 1
    import jax

    try:
        device = jax.devices()[0]
    except RuntimeError as e:
        print(f"chip_smoke: no TPU found: {e}", file=sys.stderr)
        return 1
    if device.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform is "
              f"{device.platform!r}); this smoke runs on the chip only",
              file=sys.stderr)
        return 1
    info = {"platform": device.platform, "kind": device.device_kind,
            "count": len(jax.devices())}
    _log(f"device: {json.dumps(info)}")
    _log(f"compile cache: {enable_compile_cache()}")
    t0 = now()
    try:
        run(Deployment())
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    _log(f"total wall s: {now() - t0}")
    print(json.dumps({"ok": True, "device": info}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())

"""Differential-testing harness for the batched scheduling engine.

The engine (``repro.core.engine``) must be *indistinguishable* from the
legacy per-core reference implementation it replaces: on randomized
instances spanning N, K, M, delta, demand sparsity, and heterogeneous core
rates, every algorithm is driven through ``cross_check``, which asserts
per-coflow CCT agreement (atol 1e-6; the engine reproduces the legacy float
associativity, so agreement is in fact exact), per-flow establishment-time
agreement, and independent feasibility via ``simulator.validate``.
"""
import numpy as np
import pytest

from repro.core import (
    ALGORITHMS,
    Coflow,
    Instance,
    run,
    run_batch,
    run_fast,
    sample_instance,
    synth_fb_trace,
    validate,
)
from repro.core.engine import cross_check, schedule_all_cores

N_RANDOM_INSTANCES = 54  # acceptance floor is 50


def _random_instance(trial: int) -> Instance:
    """Randomized instance; regimes rotate with the trial index.

    Covers: narrow/wide N, single- and multi-core K, dense and sparse
    demands, zero and positive reconfiguration delay, homogeneous and
    heterogeneous core rates.
    """
    rng = np.random.default_rng(1000 + trial)
    M = int(rng.integers(1, 9))
    N = int(rng.integers(2, 11))
    K = int(rng.integers(1, 6))
    sparsity = float(rng.uniform(0.1, 0.9))
    coflows = []
    for cid in range(M):
        D = rng.exponential(10, (N, N)) * (rng.random((N, N)) < sparsity)
        if not D.any():
            D[rng.integers(N), rng.integers(N)] = float(rng.exponential(10) + 0.1)
        coflows.append(Coflow(cid=cid, demand=D, weight=float(rng.integers(1, 10))))
    if trial % 3 == 0:
        rates = np.full(K, float(rng.uniform(5.0, 20.0)))   # homogeneous
    else:
        rates = np.sort(rng.uniform(1.0, 30.0, K))          # heterogeneous
    delta = 0.0 if trial % 5 == 0 else float(rng.uniform(0.0, 10.0))
    return Instance(coflows=tuple(coflows), rates=rates, delta=delta)


@pytest.mark.parametrize("trial", range(N_RANDOM_INSTANCES))
def test_engine_matches_oracle_randomized(trial):
    """All 5 algorithms on one random instance."""
    inst = _random_instance(trial)
    for alg in ALGORITHMS:
        cross_check(inst, alg, seed=trial)


@pytest.mark.slow
def test_engine_matches_oracle_trace_instance():
    """A realistic trace-driven instance (heavier than the random grid)."""
    trace = synth_fb_trace(200, seed=7)
    inst = sample_instance(trace, N=16, M=60, rates=[10, 20, 30], delta=8.0,
                           seed=3)
    for alg in ALGORITHMS:
        cross_check(inst, alg, seed=3)


def test_schedule_all_cores_matches_legacy_flow_times():
    """Beyond CCTs: every per-flow establishment time matches the oracle."""
    from repro.core import assign_tau_aware, order_coflows
    from repro.core.scheduler import _schedule_from_assignment
    from repro.core.circuit_scheduler import schedule_core_list

    inst = _random_instance(9)
    pi = order_coflows(inst)
    a = assign_tau_aware(inst, pi)
    fast = schedule_all_cores(inst, pi, a)
    legacy = _schedule_from_assignment(inst, pi, a, schedule_core_list)
    key = lambda f: (f.core, f.coflow, f.i, f.j)
    fast_by = {key(f): f for f in fast.flows}
    for f in legacy.flows:
        g = fast_by[key(f)]
        assert g.t_establish == f.t_establish
        assert g.t_start == f.t_start
        assert g.t_complete == f.t_complete


def test_engine_rejects_unknown_inputs():
    inst = _random_instance(0)
    with pytest.raises(ValueError, match="unknown algorithm"):
        run_fast(inst, "nope")
    with pytest.raises(ValueError, match="unknown backend"):
        run_fast(inst, "ours", backend="nope")
    with pytest.raises(ValueError, match="delta_k must have shape"):
        run_fast(inst, "ours", delta_k=np.full(inst.K + 1, inst.delta))


def test_run_fast_flat_path_matches_schedule_all_cores():
    """The flat production path must stay flow-for-flow identical to the
    object front-end (``schedule_all_cores`` on the dataclass assignment) —
    run_fast no longer builds that assignment, so this pins the refactor."""
    from repro.core import assign_tau_aware, order_coflows

    for trial in (2, 7, 11):
        inst = _random_instance(trial)
        pi = order_coflows(inst)
        a = assign_tau_aware(inst, pi)
        via_objects = schedule_all_cores(inst, pi, a)
        flat = run_fast(inst, "ours")
        assert flat.assignment is None  # no dataclass materialization
        assert via_objects.assignment is a
        np.testing.assert_array_equal(flat.ccts, via_objects.ccts)
        for f, g in zip(flat.flows, via_objects.flows):
            assert f == g


# --------------------------------------------------------------- run_batch

def test_run_batch_grid_shape_and_determinism():
    insts = [_random_instance(t) for t in (1, 2)]
    tab = run_batch(insts, ALGORITHMS, seeds=(0, 1), check="validate",
                    workers=0)
    # 2 insts x 2 seeds x 5 algorithms
    assert len(tab) == 2 * 2 * len(ALGORITHMS)
    # sunflow baselines are recorded under their own policy label
    assert {r.scheduling for r in tab.filter(algorithm="sunflow-core")} == {"sunflow"}
    assert {r.scheduling for r in tab.filter(algorithm="ours")} == {
        "work-conserving"}
    # deterministic: a repeat run yields identical metrics
    tab2 = run_batch(insts, ALGORITHMS, seeds=(0, 1), check="none",
                     workers=0)
    for a, b in zip(tab, tab2):
        assert a == b or (a.algorithm == b.algorithm and
                          a.weighted_cct == b.weighted_cct)


def test_run_batch_rows_match_direct_run():
    inst = _random_instance(3)
    tab = run_batch([inst], ("ours", "rand-assign"), seeds=(5,),
                    check="oracle", workers=0)
    for alg in ("ours", "rand-assign"):
        row = tab.filter(algorithm=alg).rows[0]
        s = run(inst, alg, seed=5)
        assert row.weighted_cct == pytest.approx(s.total_weighted_cct, abs=1e-9)
        assert row.makespan == pytest.approx(float(s.ccts.max()), abs=1e-9)
        assert row.n_flows == len(s.flows)


def test_run_batch_parallel_matches_serial():
    insts = [_random_instance(t) for t in (5, 6, 7)]
    kw = dict(seeds=(0, 1, 2), pair_seeds=True, check="none")
    serial = run_batch(insts, ("ours", "rand-sunflow"), workers=0, **kw)
    parallel = run_batch(insts, ("ours", "rand-sunflow"), workers=2, **kw)
    assert len(serial) == len(parallel) == 3 * 2
    for a, b in zip(serial, parallel):
        assert (a.instance, a.algorithm, a.seed) == (b.instance, b.algorithm, b.seed)
        assert a.weighted_cct == b.weighted_cct
        assert a.p99 == b.p99


def test_run_batch_pair_seeds_validation():
    insts = [_random_instance(8)]
    with pytest.raises(ValueError, match="pair_seeds"):
        run_batch(insts, ("ours",), seeds=(0, 1), pair_seeds=True)
    with pytest.raises(ValueError, match="unknown algorithms"):
        run_batch(insts, ("ours", "bogus"))


def test_result_table_helpers():
    insts = [_random_instance(t) for t in (1, 2)]
    tab = run_batch(insts, ("ours", "rho-assign"), seeds=(0,), check="none",
                    workers=0)
    sub = tab.filter(algorithm="ours")
    assert len(sub) == 2 and all(r.algorithm == "ours" for r in sub)
    w = tab.column("weighted_cct", algorithm="rho-assign")
    assert w.shape == (2,) and (w > 0).all()
    assert tab.mean("weighted_cct", algorithm="ours") == pytest.approx(
        tab.column("weighted_cct", algorithm="ours").mean())
    d = tab.to_dicts()
    assert len(d) == 4 and {"algorithm", "weighted_cct"} <= set(d[0])


def test_run_batch_validates_schedules():
    """check='validate' really exercises the independent validator."""
    inst = _random_instance(2)
    tab = run_batch([inst], ("ours",), check="validate", workers=0)
    s = run_fast(inst, "ours")
    validate(s)  # same path must hold when called directly
    assert tab.rows[0].weighted_cct == pytest.approx(s.total_weighted_cct)

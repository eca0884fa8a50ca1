"""Differential suite for the flat-array assignment engine.

The flat front-end (``coflow.extract_flows`` + ``assignment.assign_fast`` +
``engine.build_flow_table``) must be *indistinguishable* from the dataclass
oracles it replaces: on randomized instances spanning N, K, M, delta, demand
sparsity, and heterogeneous core rates, the extraction order and the per-flow
core choices of every policy are asserted bit-identical, and the end-to-end
engine paths (``run_fast`` / ``run_fast_online`` / ``run_fast_metrics`` /
``run_batch(materialize="metrics")``) are gated against the legacy oracle by
``cross_check`` — on both the numpy backend and the interpret-mode Pallas
backend.
"""
import numpy as np
import pytest

from repro.core import (
    ALGORITHMS,
    Coflow,
    Instance,
    OnlineInstance,
    assign_fast,
    assign_random,
    assign_rho_only,
    assign_tau_aware,
    assignment_from_choices,
    extract_flows,
    order_coflows,
    run_batch,
    run_fast,
    run_fast_metrics,
    run_fast_online,
    sample_instance,
    synth_fb_trace,
)
from repro.core.coflow import nonzero_flows
from repro.core.engine import build_flow_table, cross_check, cross_check_online

POLICIES = ("tau-aware", "rho-only", "random")
ORACLES = {"tau-aware": assign_tau_aware, "rho-only": assign_rho_only,
           "random": assign_random}
N_RANDOM_INSTANCES = 30


def _random_instance(trial: int) -> Instance:
    """Same regime rotation as tests/test_engine_differential.py."""
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
        rates = np.full(K, float(rng.uniform(5.0, 20.0)))
    else:
        rates = np.sort(rng.uniform(1.0, 30.0, K))
    delta = 0.0 if trial % 5 == 0 else float(rng.uniform(0.0, 10.0))
    return Instance(coflows=tuple(coflows), rates=rates, delta=delta)


def _oracle_flat(a) -> tuple:
    """Flatten a dataclass Assignment into extraction-order arrays."""
    pos, cid, fi, fj, size, core = [], [], [], [], [], []
    for per in a.flows:
        for af in per:
            pos.append(af.flow.coflow)
            cid.append(af.flow.cid)
            fi.append(af.flow.i)
            fj.append(af.flow.j)
            size.append(af.flow.size)
            core.append(af.core)
    return (np.array(pos), np.array(cid), np.array(fi), np.array(fj),
            np.array(size), np.array(core))


# ----------------------------------------------------------- extraction

@pytest.mark.parametrize("trial", range(N_RANDOM_INSTANCES))
def test_extract_flows_matches_nonzero_flows(trial):
    inst = _random_instance(trial)
    pi = order_coflows(inst)
    pos, cid, fi, fj, size = extract_flows(inst, pi)
    t = 0
    for p, ci in enumerate(pi):
        for f in nonzero_flows(inst.coflows[int(ci)], order_pos=p,
                               largest_first=True):
            assert (int(pos[t]), int(cid[t]), int(fi[t]), int(fj[t])) == \
                (f.coflow, f.cid, f.i, f.j)
            assert float(size[t]) == f.size
            t += 1
    assert t == pos.size


def test_extract_flows_empty_instance():
    inst = Instance(coflows=(), rates=np.array([10.0, 20.0]), delta=1.0)
    pos, cid, fi, fj, size = extract_flows(inst, order_coflows(inst))
    assert pos.size == cid.size == fi.size == fj.size == size.size == 0


def test_extract_flows_respects_noncontiguous_cids():
    """Coflow.cid is a free field (subset instances keep their original
    ids); the cid column must come from the Coflow, not from pi."""
    base = _random_instance(4)
    offset = tuple(
        Coflow(cid=c.cid + 100, demand=c.demand, weight=c.weight)
        for c in base.coflows)
    inst = Instance(coflows=offset, rates=base.rates, delta=base.delta)
    pi = order_coflows(inst)
    _pos, cid, *_ = extract_flows(inst, pi)
    want = np.concatenate([
        [f.cid for f in nonzero_flows(inst.coflows[int(c)], order_pos=p)]
        for p, c in enumerate(pi)]) if cid.size else cid
    np.testing.assert_array_equal(cid, want)
    assert cid.size == 0 or cid.min() >= 100


# ----------------------------------------------------- choice bit-identity

@pytest.mark.parametrize("policy", POLICIES)
@pytest.mark.parametrize("trial", range(N_RANDOM_INSTANCES))
def test_assign_fast_bit_identical_to_oracle(trial, policy):
    inst = _random_instance(trial)
    pi = order_coflows(inst)
    oracle = ORACLES[policy]
    a = oracle(inst, pi, seed=trial) if policy == "random" else oracle(inst, pi)
    *_, oracle_core = _oracle_flat(a)
    got = assign_fast(inst, pi, policy, seed=trial)
    np.testing.assert_array_equal(got, oracle_core)


def test_assign_fast_trace_instance_all_policies():
    """Trace-scale workload (heavier sizes, realistic sparsity)."""
    trace = synth_fb_trace(120, seed=11)
    inst = sample_instance(trace, N=16, M=40, rates=[10, 20, 30], delta=8.0,
                           seed=2)
    pi = order_coflows(inst)
    for policy in POLICIES:
        oracle = ORACLES[policy]
        a = oracle(inst, pi, seed=7) if policy == "random" else oracle(inst, pi)
        *_, oracle_core = _oracle_flat(a)
        np.testing.assert_array_equal(assign_fast(inst, pi, policy, seed=7),
                                      oracle_core)


def test_assign_fast_matches_kernel_ref():
    """Third implementation in lock-step: the kernel's fp64 numpy oracle."""
    from repro.kernels.ref import assign_ref

    inst = _random_instance(7)
    pi = order_coflows(inst)
    flows = extract_flows(inst, pi)
    _pos, _cid, fi, fj, size = flows
    ref_c, _ = assign_ref(fi, fj, size, inst.rates, inst.delta, inst.N)
    np.testing.assert_array_equal(
        assign_fast(inst, pi, "tau-aware", flows=flows),
        ref_c.astype(np.int64))


def test_assign_fast_rejects_unknown_policy():
    inst = _random_instance(0)
    with pytest.raises(ValueError, match="unknown policy"):
        assign_fast(inst, order_coflows(inst), "nope")


def test_assignment_from_choices_round_trip():
    """Materialized Assignment == the dataclass oracle, state included."""
    inst = _random_instance(5)
    pi = order_coflows(inst)
    flows = extract_flows(inst, pi)
    choices = assign_fast(inst, pi, "tau-aware", flows=flows)
    a = assignment_from_choices(inst, pi, flows, choices)
    want = assign_tau_aware(inst, pi)
    assert a.flows == want.flows
    np.testing.assert_array_equal(a.state.bound, want.state.bound)
    np.testing.assert_array_equal(a.state.row_load, want.state.row_load)
    np.testing.assert_array_equal(a.state.nz, want.state.nz)


# ------------------------------------------------- end-to-end, numpy backend

@pytest.mark.parametrize("trial", range(0, N_RANDOM_INSTANCES, 3))
def test_run_fast_numpy_backend_cross_check(trial):
    """Flat engine vs legacy oracle (choices, CCTs, flow times, validator)."""
    inst = _random_instance(trial)
    for alg in ALGORITHMS:
        cross_check(inst, alg, seed=trial, backend="numpy")


def test_run_fast_metrics_matches_run_fast():
    for trial in (1, 4, 8):
        inst = _random_instance(trial)
        rel = np.random.default_rng(trial).exponential(5.0, inst.M)
        for alg in ALGORITHMS:
            s = run_fast(inst, alg, seed=trial)
            ccts, n_flows = run_fast_metrics(inst, alg, seed=trial)
            np.testing.assert_array_equal(ccts, s.ccts)
            assert n_flows == len(s.flows)
            so = run_fast_online(OnlineInstance(inst=inst, releases=rel),
                                 alg, seed=trial)
            ccts_o, n_o = run_fast_metrics(inst, alg, seed=trial, releases=rel)
            np.testing.assert_array_equal(ccts_o, so.ccts)
            assert n_o == len(so.flows)


def test_run_batch_metrics_mode_matches_full():
    insts = [_random_instance(t) for t in (2, 6)]
    rel = np.random.default_rng(0).exponential(5.0, insts[1].M)
    kw = dict(seeds=(0, 1), schedulings=("work-conserving", "reserving"),
              workers=0, releases=(None, rel))
    full = run_batch(insts, ALGORITHMS, check="validate", **kw)
    metrics = run_batch(insts, ALGORITHMS, check="none",
                        materialize="metrics", **kw)
    assert len(full) == len(metrics) > 0
    for a, b in zip(full, metrics):
        assert (a.instance, a.algorithm, a.scheduling, a.seed) == \
            (b.instance, b.algorithm, b.scheduling, b.seed)
        assert a.weighted_cct == b.weighted_cct
        assert a.total_cct == b.total_cct
        assert a.p95 == b.p95 and a.p99 == b.p99
        assert a.makespan == b.makespan and a.n_flows == b.n_flows


def test_run_batch_metrics_mode_requires_check_none():
    inst = _random_instance(0)
    with pytest.raises(ValueError, match="metrics"):
        run_batch([inst], ("ours",), materialize="metrics", workers=0)
    with pytest.raises(ValueError, match="unknown materialize"):
        run_batch([inst], ("ours",), materialize="bogus", workers=0)
    with pytest.raises(ValueError, match="unknown backend"):
        run_batch([inst], ("ours",), backend="bogus", workers=0)


def test_vectorized_random_draws_match_sequential():
    """The one RNG assumption of the flat random policy, asserted directly:
    Generator.choice(size=F) consumes the PCG64 stream exactly like F
    sequential scalar draws."""
    p = np.array([5.0, 10.0, 20.0, 25.0])
    p = p / p.sum()
    a, b = np.random.default_rng(42), np.random.default_rng(42)
    seq = np.array([a.choice(4, p=p) for _ in range(500)])
    vec = b.choice(4, size=500, p=p)
    np.testing.assert_array_equal(seq, vec)


# ------------------------------------------------------------ pallas backend

def test_run_fast_pallas_backend_cross_check():
    """Kernel-assigned engine path vs assign_ref gate + legacy replay.

    Interpret mode (CPU container); on TPU the same calls compile to Mosaic.
    """
    inst = _random_instance(3)
    for alg in ("ours", "sunflow-core", "rho-assign"):
        cross_check(inst, alg, seed=3, backend="pallas")


def test_run_fast_pallas_online_cross_check():
    inst = _random_instance(6)
    rel = np.random.default_rng(6).exponential(5.0, inst.M)
    oinst = OnlineInstance(inst=inst, releases=rel)
    cross_check_online(oinst, "ours", seed=6, backend="pallas")


def test_run_batch_oracle_both_backends():
    """Acceptance gate: run_batch(check="oracle") end-to-end, both backends."""
    inst = _random_instance(1)
    for backend in ("numpy", "pallas"):
        tab = run_batch([inst], ("ours", "rand-assign"), check="oracle",
                        workers=0, backend=backend)
        assert len(tab) == 2 and all(r.weighted_cct > 0 for r in tab)


def test_run_batch_pallas_stays_in_process():
    """A chip belongs to one process: pallas sweeps never fan out."""
    insts = [_random_instance(s) for s in range(4)]
    tab = run_batch(insts, ("ours",), check="none", backend="pallas",
                    materialize="metrics")  # default workers: 4 grid points
    assert len(tab) == 4
    with pytest.raises(ValueError, match="holds the chip"):
        run_batch(insts, ("ours",), check="none", backend="pallas",
                  materialize="metrics", workers=2)


def test_build_flow_table_backends_agree_small():
    """fp32 vs fp64 tie decisions agree on a small instance."""
    inst = _random_instance(2)
    pi = order_coflows(inst)
    t_np = build_flow_table(inst, pi, "ours", backend="numpy")
    t_pl = build_flow_table(inst, pi, "ours", backend="pallas")
    np.testing.assert_array_equal(t_np.core, t_pl.core)
    np.testing.assert_array_equal(t_np.pos, t_pl.pos)


# ----------------------------------------------------- M = 0 regression

def test_run_batch_empty_instance_zero_metrics():
    """M == 0 used to crash in simulator.validate (np.stack of an empty
    list) and in the p95/p99 tail quantiles; it must yield a zero row."""
    empty = Instance(coflows=(), rates=np.array([10.0, 20.0]), delta=2.0)
    for check in ("validate", "oracle"):
        tab = run_batch([empty], ALGORITHMS, check=check, workers=0)
        assert len(tab) == len(ALGORITHMS)
        for r in tab:
            assert r.weighted_cct == r.total_cct == 0.0
            assert r.p95 == r.p99 == r.makespan == 0.0
            assert r.n_flows == 0
    tab = run_batch([empty], ALGORITHMS, check="none", workers=0,
                    materialize="metrics")
    assert all(r.weighted_cct == 0.0 and r.n_flows == 0 for r in tab)


def test_run_fast_empty_instance():
    empty = Instance(coflows=(), rates=np.array([10.0]), delta=0.5)
    s = run_fast(empty, "ours")
    assert s.ccts.size == 0 and s.flows == []
    ccts, n_flows = run_fast_metrics(empty, "ours")
    assert ccts.size == 0 and n_flows == 0


def test_theory_checks_reject_flat_schedules_clearly():
    """Lemmas 2/3 need Schedule.assignment, which the flat path skips; they
    must fail with directions, not an AttributeError on None."""
    from repro.core import check_lemma1, check_theorem1
    from repro.core.theory import check_lemma2, check_lemma3

    inst = _random_instance(3)
    s = run_fast(inst, "ours")
    check_lemma1(s)     # ccts-only certificates still work on flat schedules
    check_theorem1(s)
    for check in (check_lemma2, check_lemma3):
        with pytest.raises(ValueError, match="scheduler.run"):
            check(s)


# ------------------------------------------------ empty-filter regression

def test_result_table_empty_filter_raises():
    """A filter matching nothing used to emit two numpy RuntimeWarnings and
    return NaN from mean(); it must raise a ValueError naming the filter."""
    import warnings

    inst = _random_instance(0)
    tab = run_batch([inst], ("ours",), check="none", workers=0)
    with pytest.raises(ValueError, match="algorithm.*bogus"):
        tab.column("weighted_cct", algorithm="bogus")
    with pytest.raises(ValueError, match="no rows match"):
        tab.mean("weighted_cct", algorithm="ours", seed=999)
    with warnings.catch_warnings():
        warnings.simplefilter("error")  # any RuntimeWarning -> failure
        try:
            tab.mean("p99", scheduling="nope")
        except ValueError:
            pass
    # the non-empty path still works
    assert tab.mean("weighted_cct", algorithm="ours") > 0

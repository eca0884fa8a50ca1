"""Unit tests for the paper's core: demand math, bounds, ordering, assignment,
and the not-all-stop circuit schedulers."""
import numpy as np
import pytest

from repro.core import (
    Coflow,
    Instance,
    assign_random,
    assign_rho_only,
    assign_tau_aware,
    global_lb,
    order_coflows,
    per_core_lb,
    rho,
    run,
    tau,
    validate,
)
from repro.core.lower_bounds import CoreState
from repro.core.ordering import priority_scores


def mk_inst(demands, rates=(10, 20, 30), delta=8.0, weights=None):
    cs = []
    for idx, d in enumerate(demands):
        w = 1.0 if weights is None else weights[idx]
        cs.append(Coflow(cid=idx, demand=np.asarray(d, dtype=float), weight=w))
    return Instance(coflows=tuple(cs), rates=np.asarray(rates, float), delta=delta)


class TestDemandMath:
    def test_rho_tau_simple(self):
        D = np.array([[2.0, 3.0], [0.0, 5.0]])
        assert rho(D) == 8.0  # col 1 sum = 3 + 5
        assert tau(D) == 2

    def test_rho_row_dominated(self):
        D = np.array([[9.0, 9.0], [1.0, 0.0]])
        assert rho(D) == 18.0
        assert tau(D) == 2

    def test_zero_matrix(self):
        D = np.zeros((3, 3))
        assert rho(D) == 0.0
        assert tau(D) == 0

    def test_coflow_validation(self):
        with pytest.raises(ValueError):
            Coflow(cid=0, demand=np.ones((2, 3)))
        with pytest.raises(ValueError):
            Coflow(cid=0, demand=-np.ones((2, 2)))
        with pytest.raises(ValueError):
            Coflow(cid=0, demand=np.ones((2, 2)), weight=0.0)


class TestLowerBounds:
    def test_per_core_lb_hand_computed(self):
        # D: port loads row0=5, row1=7, col0=2, col1=10; taus row=(2,1), col=(1,2)
        D = np.array([[2.0, 3.0], [0.0, 7.0]])
        r, delta = 2.0, 1.0
        # L_row0 = 5/2 + 2 = 4.5 ; L_row1 = 7/2 + 1 = 4.5
        # L_col0 = 2/2 + 1 = 2   ; L_col1 = 10/2 + 2 = 7
        assert per_core_lb(D, r, delta) == pytest.approx(7.0)

    def test_global_lb_hand_computed(self):
        D = np.array([[2.0, 3.0], [0.0, 7.0]])
        assert global_lb(D, R=60.0, delta=8.0) == pytest.approx(8.0 + 10.0 / 60.0)

    def test_per_core_lb_zero(self):
        assert per_core_lb(np.zeros((4, 4)), 10.0, 8.0) == 0.0

    def test_core_state_incremental_matches_batch(self):
        rng = np.random.default_rng(0)
        K, N = 3, 8
        rates = np.array([10.0, 20.0, 30.0])
        st = CoreState(K=K, N=N, rates=rates, delta=8.0)
        mats = np.zeros((K, N, N))
        for _ in range(200):
            i, j, k = rng.integers(0, N), rng.integers(0, N), rng.integers(0, K)
            d = float(rng.uniform(0.1, 5.0))
            st.assign(int(i), int(j), d, int(k))
            mats[k, i, j] += d
        for k in range(K):
            assert st.bound[k] == pytest.approx(per_core_lb(mats[k], rates[k], 8.0))

    def test_candidate_bound_matches_commit(self):
        st = CoreState(K=2, N=4, rates=np.array([10.0, 20.0]), delta=2.0)
        st.assign(0, 1, 5.0, 0)
        cand = st.candidate_bounds(0, 2, 3.0)
        st2 = CoreState(K=2, N=4, rates=np.array([10.0, 20.0]), delta=2.0)
        st2.assign(0, 1, 5.0, 0)
        st2.assign(0, 2, 3.0, 0)
        assert cand[0] == pytest.approx(st2.bound[0])


class TestOrdering:
    def test_wspt_order(self):
        # Coflow 0: heavy, low weight. Coflow 1: tiny, high weight.
        big = np.full((2, 2), 100.0)
        small = np.array([[1.0, 0.0], [0.0, 0.0]])
        inst = mk_inst([big, small], weights=[1.0, 10.0])
        pi = order_coflows(inst)
        assert list(pi) == [1, 0]

    def test_scores_formula(self):
        D = np.array([[6.0, 0.0], [0.0, 0.0]])
        inst = mk_inst([D], rates=(10, 20, 30), delta=8.0, weights=[5.0])
        s = priority_scores(inst)
        assert s[0] == pytest.approx(5.0 / (8.0 + 6.0 / 60.0))

    def test_stable_tiebreak(self):
        D = np.array([[6.0, 0.0], [0.0, 0.0]])
        inst = mk_inst([D, D, D])
        assert list(order_coflows(inst)) == [0, 1, 2]


class TestAssignment:
    def test_all_demand_assigned(self):
        rng = np.random.default_rng(1)
        demands = [rng.uniform(0, 4, (6, 6)) * (rng.random((6, 6)) < 0.4) for _ in range(5)]
        inst = mk_inst(demands)
        pi = order_coflows(inst)
        for assign in (assign_tau_aware, assign_rho_only):
            a = assign(inst, pi)
            for pos, ci in enumerate(pi):
                got = a.per_core_demand(pos).sum(axis=0)
                np.testing.assert_allclose(got, inst.coflows[int(ci)].demand, atol=1e-9)

    def test_no_flow_splitting(self):
        rng = np.random.default_rng(2)
        D = rng.uniform(1, 5, (4, 4))
        inst = mk_inst([D])
        a = assign_tau_aware(inst, order_coflows(inst))
        per_core = a.per_core_demand(0)
        # every (i,j) must be nonzero on exactly one core
        nz_count = (per_core > 0).sum(axis=0)
        assert (nz_count == 1).all()

    def test_greedy_picks_argmin_core(self):
        # Single flow: must land on the fastest core (min d/r + delta).
        D = np.zeros((3, 3))
        D[0, 1] = 30.0
        inst = mk_inst([D], rates=(10, 20, 30), delta=8.0)
        a = assign_tau_aware(inst, order_coflows(inst))
        assert a.flows[0][0].core == 2

    def test_tau_awareness_spreads_circuits(self):
        # Many equal tiny flows on one ingress port, homogeneous cores:
        # tau-aware must spread them across cores instead of stacking.
        N, F = 8, 6
        D = np.zeros((N, N))
        D[0, :F] = 0.001
        inst = mk_inst([D], rates=(10, 10, 10), delta=8.0)
        a = assign_tau_aware(inst, order_coflows(inst))
        cores = [af.core for af in a.flows[0]]
        counts = np.bincount(cores, minlength=3)
        assert counts.max() - counts.min() <= 1

    def test_prefix_per_core_cached_matches_scratch_rebuild(self):
        """The cached cumulative prefix must equal the old from-scratch
        rebuild bit-for-bit, for forward scans, repeats, and backward
        jumps — and a scan over all prefixes must not mutate earlier
        results (returned arrays are copies)."""
        rng = np.random.default_rng(7)
        demands = [rng.uniform(0, 4, (5, 5)) * (rng.random((5, 5)) < 0.5)
                   for _ in range(6)]
        for d in demands:
            if not d.any():
                d[0, 0] = 1.0
        inst = mk_inst(demands)
        pi = order_coflows(inst)
        a = assign_tau_aware(inst, pi)

        def scratch(m_pos):  # the pre-cache implementation, verbatim
            out = np.zeros((inst.K, inst.N, inst.N))
            for p in range(m_pos + 1):
                for af in a.flows[p]:
                    out[af.core, af.flow.i, af.flow.j] += af.flow.size
            return out

        # forward scan (the theory-check pattern), with a repeat and
        # backward jumps interleaved
        for m in [0, 1, 2, 2, 5, 3, 0, 4, 5]:
            np.testing.assert_array_equal(a.prefix_per_core(m), scratch(m))
        first = a.prefix_per_core(0)
        a.prefix_per_core(5)[:] = -1.0  # mutate a returned copy
        np.testing.assert_array_equal(a.prefix_per_core(0), first)
        # consistency with the per-coflow increments
        total = sum(a.per_core_demand(p) for p in range(len(demands)))
        np.testing.assert_allclose(a.prefix_per_core(5), total, atol=1e-12)

    def test_random_assignment_rate_proportional(self):
        N = 4
        D = np.full((N, N), 1.0)
        inst = mk_inst([D] * 50, rates=(10, 20, 30), delta=1.0)
        a = assign_random(inst, order_coflows(inst), seed=3)
        cores = np.array([af.core for per in a.flows for af in per])
        frac = np.bincount(cores, minlength=3) / len(cores)
        np.testing.assert_allclose(frac, [1 / 6, 2 / 6, 3 / 6], atol=0.05)


class TestCircuitScheduling:
    def test_single_flow_timing(self):
        D = np.zeros((2, 2))
        D[0, 1] = 30.0
        inst = mk_inst([D], rates=(10, 20, 30), delta=8.0)
        s = run(inst, "ours")
        validate(s)
        f = s.flows[0]
        assert f.t_establish == 0.0
        assert f.t_start == 8.0
        assert f.t_complete == pytest.approx(8.0 + 30.0 / 30.0)

    def test_port_conflict_serializes(self):
        # Two flows sharing ingress port 0 on a single core must serialize.
        D = np.zeros((2, 2))
        D[0, 0] = 10.0
        D[0, 1] = 10.0
        inst = mk_inst([D], rates=(10,), delta=2.0)
        s = run(inst, "ours")
        validate(s)
        times = sorted((f.t_establish, f.t_complete) for f in s.flows)
        assert times[1][0] >= times[0][1] - 1e-9

    def test_disjoint_flows_parallel(self):
        D = np.zeros((2, 2))
        D[0, 0] = 10.0
        D[1, 1] = 10.0
        inst = mk_inst([D], rates=(10,), delta=2.0)
        s = run(inst, "ours")
        assert all(f.t_establish == 0.0 for f in s.flows)

    def test_work_conservation_backfills(self):
        # Coflow A (priority) occupies (0,0); coflow B's flow (1,1) is disjoint
        # and must start at t=0 under the work-conserving policy.
        A = np.zeros((2, 2)); A[0, 0] = 100.0
        B = np.zeros((2, 2)); B[1, 1] = 1.0
        inst = mk_inst([A, B], rates=(10,), delta=2.0, weights=[10.0, 1.0])
        s = run(inst, "ours")
        b_flow = [f for f in s.flows if f.size == 1.0][0]
        assert b_flow.t_establish == 0.0

    def test_sunflow_barrier_blocks_overlap(self):
        A = np.zeros((2, 2)); A[0, 0] = 100.0
        B = np.zeros((2, 2)); B[1, 1] = 1.0
        inst = mk_inst([A, B], rates=(10,), delta=2.0, weights=[10.0, 1.0])
        s = run(inst, "sunflow-core")
        validate(s)
        a_done = max(f.t_complete for f in s.flows if f.size == 100.0)
        b_flow = [f for f in s.flows if f.size == 1.0][0]
        assert b_flow.t_establish >= a_done - 1e-9

    def test_all_algorithms_feasible(self):
        rng = np.random.default_rng(4)
        demands = [
            rng.uniform(0, 20, (8, 8)) * (rng.random((8, 8)) < 0.3) for _ in range(10)
        ]
        inst = mk_inst(demands, weights=list(rng.integers(1, 11, 10).astype(float)))
        from repro.core import ALGORITHMS

        for alg in ALGORITHMS:
            s = run(inst, alg, seed=5)
            validate(s)


class TestCCTSemantics:
    def test_cct_is_max_over_cores_and_flows(self):
        rng = np.random.default_rng(6)
        D = rng.uniform(1, 10, (5, 5)) * (rng.random((5, 5)) < 0.5)
        inst = mk_inst([D])
        s = run(inst, "ours")
        assert s.ccts[0] == pytest.approx(max(f.t_complete for f in s.flows))

    def test_empty_coflow_has_zero_cct(self):
        Z = np.zeros((3, 3))
        D = np.zeros((3, 3)); D[0, 0] = 5.0
        inst = mk_inst([Z, D])
        s = run(inst, "ours")
        validate(s)
        assert s.ccts[0] == 0.0
        assert s.ccts[1] > 0.0

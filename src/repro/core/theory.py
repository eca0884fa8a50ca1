"""Executable certificates for the paper's guarantees (Lemmas 1-3, Thms 1-2, Lemma 6).

Each ``check_*`` returns a dict of the quantities involved and raises
AssertionError when the proven inequality is violated — these run in the test
suite over randomized instances (hypothesis) and over the trace-driven
benchmark instances.
"""
from __future__ import annotations

import numpy as np

from .coflow import Instance, rho, tau
from .lower_bounds import global_lb, per_core_lb
from .scheduler import Schedule

__all__ = [
    "gamma_w",
    "check_lemma1",
    "check_lemma2",
    "check_lemma3",
    "check_theorem1",
    "check_theorem2",
]


def gamma_w(weights: np.ndarray) -> float:
    """Weight concentration parameter Gamma_w = M * sum(w^2) / (sum w)^2."""
    w = np.asarray(weights, dtype=np.float64)
    return float(len(w) * (w**2).sum() / (w.sum() ** 2))


def _require_assignment(s: Schedule) -> None:
    """Lemmas 2/3 charge prefix traffic per core, which needs the per-coflow
    AssignedFlow lists. The flat engine path (``engine.run_fast``) does not
    materialize them — fail with directions rather than an AttributeError."""
    if s.assignment is None:
        raise ValueError(
            "this certificate needs Schedule.assignment, which the flat "
            "engine path does not materialize; build the schedule via "
            "scheduler.run or engine.schedule_all_cores instead")
    return s.assignment


def check_lemma1(s: Schedule) -> dict:
    """T_m >= T_LB(D_m) = delta + rho_m / R for every coflow (any feasible schedule)."""
    inst = s.inst
    lbs = np.array([global_lb(c.demand, inst.R, inst.delta) for c in inst.coflows])
    ok = s.ccts + 1e-9 >= lbs
    # Zero-demand coflows have LB 0 and CCT 0.
    if not ok.all():
        bad = np.nonzero(~ok)[0]
        raise AssertionError(f"Lemma 1 violated for coflows {bad}: cct={s.ccts[bad]} lb={lbs[bad]}")
    return {"ccts": s.ccts, "lbs": lbs}


def _prefix_stats(inst: Instance, pi: np.ndarray, m_pos: int) -> tuple[float, int]:
    D = np.zeros((inst.N, inst.N))
    for p in range(m_pos + 1):
        D += inst.coflows[int(pi[p])].demand
    return rho(D), tau(D)


def check_lemma2(s: Schedule) -> dict:
    """max_k T_LB^k(D^k_{1:m}) <= rho_{1:m}/r_max + tau_{1:m}*delta for every m.

    Only guaranteed for the paper's tau-aware assignment (greedy argmin on
    T_LB^k), i.e. algorithms 'ours' and 'sunflow-core'.
    """
    inst, pi, a = s.inst, s.pi, _require_assignment(s)
    out = []
    prefix = np.zeros((inst.K, inst.N, inst.N))
    agg = np.zeros((inst.N, inst.N))
    for m_pos in range(inst.M):
        for af in a.flows[m_pos]:
            prefix[af.core, af.flow.i, af.flow.j] += af.flow.size
        agg += inst.coflows[int(pi[m_pos])].demand
        lhs = max(
            per_core_lb(prefix[k], float(inst.rates[k]), inst.delta) for k in range(inst.K)
        )
        rhs = rho(agg) / inst.r_max + tau(agg) * inst.delta
        out.append((lhs, rhs))
        if lhs > rhs + 1e-6:
            raise AssertionError(f"Lemma 2 violated at m={m_pos}: {lhs} > {rhs}")
    return {"pairs": out}


def check_lemma3(s: Schedule, *, strict: bool = True) -> dict:
    """T_pi(m) <= 2 * max_k T_LB^k(D^k_{1:m}) for the work-conserving scheduler.

    REPRODUCTION FINDING (quantified in tests/test_theory.py and
    EXPERIMENTS.md): the paper's proof charges the busy time of the last
    flow's ports to *prefix* traffic only, but the literal non-preemptive
    work-conserving policy of Alg. 1 (lines 23-31) lets lower-priority
    (non-prefix) flows occupy ports, so the inequality fails systematically
    once multiple coflows interleave — the worst observed ratio grows
    ~linearly with M (x2.4 at M=5, x13.6 at M=50 on random instances; ~x6 on
    trace workloads at M=50). It DOES hold for single coflows (where the
    proof's charging argument is airtight), and Theorem 1's end-to-end bound
    (which carries a 2*M*psi slack) still holds empirically on every instance
    we tested. Neither a guard across coflows nor strict in-order
    reservation repaired the lemma when measured (EXPERIMENTS.md
    §Reproduction-notes), so neither is kept. ``strict=False`` returns
    violations instead of raising.
    """
    inst, pi, a = s.inst, s.pi, _require_assignment(s)
    # completion per coflow position
    t_pos = np.zeros(inst.M)
    for f in s.flows:
        t_pos[f.coflow] = max(t_pos[f.coflow], f.t_complete)
    prefix = np.zeros((inst.K, inst.N, inst.N))
    pairs = []
    violations = []
    for m_pos in range(inst.M):
        for af in a.flows[m_pos]:
            prefix[af.core, af.flow.i, af.flow.j] += af.flow.size
        bound = 2 * max(
            per_core_lb(prefix[k], float(inst.rates[k]), inst.delta) for k in range(inst.K)
        )
        pairs.append((t_pos[m_pos], bound))
        if t_pos[m_pos] > bound + 1e-6:
            violations.append((m_pos, float(t_pos[m_pos]), float(bound)))
    if strict and violations:
        raise AssertionError(f"Lemma 3 violated at (m, T, bound): {violations[:5]}")
    return {"pairs": pairs, "violations": violations}


def check_theorem1(s: Schedule) -> dict:
    """sum w T <= 2 M (w_max/w_min) psi * sum w T_LB  (stronger than vs OPT)."""
    inst = s.inst
    lbs = np.array([global_lb(c.demand, inst.R, inst.delta) for c in inst.coflows])
    w = inst.weights
    lhs = float((w * s.ccts).sum())
    # Coflows with zero demand contribute 0 to both sides.
    denom = float((w * lbs).sum())
    ratio_bound = 2 * inst.M * (w.max() / w.min()) * inst.psi
    if denom > 0 and lhs > ratio_bound * denom + 1e-6:
        raise AssertionError(f"Theorem 1 violated: {lhs} > {ratio_bound} * {denom}")
    return {"alg": lhs, "lb_sum": denom, "bound": ratio_bound,
            "empirical_ratio": lhs / denom if denom > 0 else float("nan")}


def check_theorem2(s: Schedule, *, strict: bool = True) -> dict:
    """sum w T <= 2 psi Gamma_w * sum w T_LB (appendix refinement, Eq. 41).

    REPRODUCTION FINDING: this refinement cannot hold in general — with equal
    weights Gamma_w = 1 and the bound becomes M-independent (2*psi), yet M
    identical coflows on one core necessarily complete at times 1..M, giving
    an average ratio ~M/2 (see tests/test_theory.py::
    test_theorem2_eq41_deterministic_counterexample). The gap is Lemma 5's
    concentration step (Eq. 37). ``strict=False`` reports instead of raising.
    """
    inst = s.inst
    lbs = np.array([global_lb(c.demand, inst.R, inst.delta) for c in inst.coflows])
    w = inst.weights
    lhs = float((w * s.ccts).sum())
    denom = float((w * lbs).sum())
    bound = 2 * inst.psi * gamma_w(w)
    if strict and denom > 0 and lhs > bound * denom + 1e-6:
        raise AssertionError(f"Theorem 2 violated: {lhs} > {bound} * {denom}")
    return {"alg": lhs, "lb_sum": denom, "bound": bound,
            "empirical_ratio": lhs / denom if denom > 0 else float("nan")}

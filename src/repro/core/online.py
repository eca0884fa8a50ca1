"""Online extension: coflows with release (arrival) times — the paper's
stated future-work direction (§VI), built on the same per-core machinery.

Model: coflow C_m becomes known at ``release_m``; nothing of it may be
assigned or scheduled earlier (clairvoyance only of arrived coflows, as in
the standard online coflow model, and as in the related parallel-network
coflow work — Chen's non-splitting heterogeneous-network scheduler and the
O(K)-approximation K-core OCS scheduler — which both treat online WSPT
re-ranking as the baseline online policy). We implement an event-driven
online scheduler:

  - on each arrival, the new coflow is ordered among the *pending* (arrived,
    unfinished) coflows by the paper's WSPT score w_m / T_LB(D_m) — a heavy
    late arrival with a higher score therefore JUMPS AHEAD of every pending
    lower-score coflow. Because the WSPT score of a coflow never changes,
    re-ranking the pending set at each arrival is equivalent to one static
    priority ranking of all coflows by score (completed coflows have no
    pending flows, so their rank is moot); eligibility is what arrivals
    gate.
  - its flows are assigned to cores at arrival by the same tau-aware greedy
    rule (or the rho-only / random baselines), against the *current* prefix
    state, processing coflows in arrival order (ties broken by WSPT score);
    assignment is irrevocable — matching the offline algorithm's per-flow
    commitment;
  - each core's circuit scheduler is the not-all-stop list scheduler with
    flows scanned in WSPT priority order and eligibility gated on release
    times (a flow may establish only at or after its coflow's release). All
    time comparisons are exact floats — same convention as
    ``circuit_scheduler`` (a flow is released iff ``release <= t``).

With all releases 0 the arrival order, the priority order, and the offline
order ``order_coflows(inst)`` coincide, so ``run_online`` reduces to the
offline ``scheduler.run`` bit-for-bit (asserted in tests).

This module is the *reference oracle* for the online path. The production
path is ``engine.run_fast_online`` (the vectorized all-cores event loop with
native release gating), validated against this oracle by
``engine.cross_check_online`` and tests/test_online_differential.py.

The offline Algorithm 1 on the same instance with all releases forced to 0
lower-bounds what any online policy could see, so the benchmark reports the
"price of arrival" ratio.
"""
from __future__ import annotations

import numpy as np

from .assignment import Assignment, assign_random, assign_rho_only, assign_tau_aware
from .circuit_scheduler import (
    ScheduledFlow,
    _run_list_scheduler,
    schedule_core_list,
)
from .coflow import Instance, OnlineInstance
from .ordering import priority_scores
from .scheduler import ALGORITHMS, SUNFLOW_ALGORITHMS, Schedule

__all__ = ["OnlineInstance", "run_online", "online_orders"]


def online_orders(inst: Instance, rel: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """(arrival order, priority rank) shared by the oracle and the engine.

    Arrival order: coflow indices sorted by (release, -WSPT score, index) —
    the order in which coflows are assigned to cores (assignment happens at
    arrival and is irrevocable; simultaneous arrivals are assigned in WSPT
    order, so ``releases == 0`` reproduces the offline order exactly).

    Priority rank: ``prio_rank[orig_id]`` = position of the coflow in the
    WSPT ordering of ALL coflows (score descending, stable by index). This
    is the scheduling priority — re-ranking the pending set by WSPT at each
    arrival is equivalent to this static ranking (scores are
    time-invariant), which is what makes a vectorized engine path possible.
    """
    s = priority_scores(inst)
    arrival = np.lexsort((-s, rel))
    prio_order = np.argsort(-s, kind="stable")
    prio_rank = np.empty(inst.M, dtype=np.int64)
    prio_rank[prio_order] = np.arange(inst.M)
    return arrival, prio_rank


def _assign_at_arrival(inst: Instance, arrival: np.ndarray, algorithm: str,
                       seed: int) -> Assignment:
    """Per-arrival irrevocable assignment."""
    if algorithm in ("ours", "sunflow-core"):
        a = assign_tau_aware(inst, arrival)
    elif algorithm == "rho-assign":
        a = assign_rho_only(inst, arrival)
    elif algorithm in ("rand-assign", "rand-sunflow"):
        a = assign_random(inst, arrival, seed=seed)
    else:
        raise ValueError(
            f"unknown algorithm {algorithm!r}; one of {sorted(ALGORITHMS)}")
    return a


def run_online(
    oinst: OnlineInstance,
    algorithm: str = "ours",
    *,
    seed: int = 0,
    assignment: Assignment | None = None,
) -> Schedule:
    """Online tau-aware scheduling with arrivals — the reference oracle.

    Per-core Python event loops, kept deliberately simple; use
    ``engine.run_fast_online`` for anything performance-sensitive. Returns a
    Schedule whose feasibility (incl. release-time respect) is validated by
    ``simulator.validate(s, releases=...)``.

    The work-conserving scheduler scans pending *released* flows in WSPT
    priority order at every event. The sunflow baselines serve one coflow
    at a time: whenever the core frees, the highest-WSPT-score *arrived*
    unserved coflow is served next (idling until the next arrival if none is
    pending).

    ``assignment``: replay hook for the differential harness — when given,
    the per-arrival assignment phase is skipped and the provided
    :class:`Assignment` (in arrival order) is scheduled instead. This is how
    ``engine.cross_check_online`` replays the Pallas kernel's fp32 choices
    through the oracle scheduler without re-deriving them in fp64.
    """
    inst = oinst.inst
    rel = np.asarray(oinst.releases, dtype=np.float64)
    assert len(rel) == inst.M

    arrival, prio_rank = online_orders(inst, rel)
    if assignment is None:
        a = _assign_at_arrival(inst, arrival, algorithm, seed)
    else:
        a = assignment
    sunflow = algorithm in SUNFLOW_ALGORITHMS
    rel_pos = rel[arrival]          # release of the coflow at arrival position
    prio_pos = prio_rank[arrival]   # scheduling priority of that position

    all_scheduled: list[ScheduledFlow] = []
    for k in range(inst.K):
        rate = float(inst.rates[k])
        on_k = [af for per in a.flows for af in per if af.core == k]
        if sunflow:
            all_scheduled.extend(_sunflow_core_online(
                on_k, k, rate, inst.delta, inst.N, rel_pos, prio_pos))
        else:
            # WSPT priority scan order: coflow priority rank, then the
            # intra-coflow assignment (largest-first) order.
            on_k.sort(key=lambda af: prio_pos[af.flow.coflow])
            rel_f = np.array([rel_pos[af.flow.coflow] for af in on_k])
            all_scheduled.extend(schedule_core_list(
                on_k, k, rate, inst.delta, inst.N, releases=rel_f))

    ccts = np.zeros(inst.M)
    for f in all_scheduled:
        orig = int(arrival[f.coflow])
        ccts[orig] = max(ccts[orig], f.t_complete)
    return Schedule(inst=inst, pi=arrival, assignment=a, flows=all_scheduled,
                    ccts=ccts)


def _sunflow_core_online(
    flows: list,  # AssignedFlows of one core, arrival-major order
    core: int,
    rate: float,
    delta: float,
    n_ports: int,
    rel_pos: np.ndarray,
    prio_pos: np.ndarray,
) -> list[ScheduledFlow]:
    """Online SUNFLOW-CORE: coflow-at-a-time with WSPT pick-next on arrival.

    The core serves exactly one coflow at a time (barrier between coflows,
    as in ``schedule_core_sunflow``); when it frees, the arrived unserved
    coflow with the best WSPT rank is served next, idling until the next
    arrival if none is pending. With all releases 0 this reduces to the
    offline ``schedule_core_sunflow`` exactly.
    """
    groups: dict[int, list] = {}
    for af in flows:
        groups.setdefault(af.flow.coflow, []).append(af)
    # insertion-ordered dict, not a set: the ready-list scan must iterate
    # deterministically (reprolint RL104)
    unserved = dict.fromkeys(groups)
    out: list[ScheduledFlow] = []
    barrier = 0.0
    while unserved:
        ready = [p for p in unserved if rel_pos[p] <= barrier]
        if not ready:
            barrier = min(float(rel_pos[p]) for p in unserved)
            ready = [p for p in unserved if rel_pos[p] <= barrier]
        pos = min(ready, key=lambda p: prio_pos[p])
        del unserved[pos]
        grp = sorted(groups[pos], key=lambda af: (-af.flow.size, af.flow.i,
                                                  af.flow.j))
        fi = np.array([af.flow.i for af in grp], dtype=np.int64)
        fj = np.array([af.flow.j for af in grp], dtype=np.int64)
        sizes = np.array([af.flow.size for af in grp], dtype=np.float64)
        t_est = _run_list_scheduler(fi, fj, sizes, rate, delta, n_ports,
                                    t0=barrier, guard=True)
        for idx, af in enumerate(grp):
            te = float(t_est[idx])
            tc = te + delta + af.flow.size / rate
            out.append(ScheduledFlow(
                coflow=af.flow.coflow, cid=af.flow.cid, i=af.flow.i,
                j=af.flow.j, core=core, size=af.flow.size, t_establish=te,
                t_start=te + delta, t_complete=tc))
            barrier = max(barrier, tc)
    return out

"""Vectorized batched scheduling engine (fast path for Algorithm 1's phase 3).

``circuit_scheduler._run_list_scheduler`` is an event loop that rescans every
pending flow in a Python ``for`` at every event — O(events x pending) Python
iterations, ~18 s for a single N=32, M=200 trace instance. This module
replaces that inner scan with numpy mask arithmetic and schedules *all K
cores in one call* by mapping each (core, port) pair to a distinct resource
id, so one merged event loop drives the whole machine:

  - port availability lives in two flat ``(K*N,)`` float arrays (ingress and
    egress resources are independent, as in the paper's OCS model);
  - per event, the set of flows that the sequential priority scan would start
    is computed with vector masks: a flow starts iff it is the first pending
    candidate on *both* its resources (iterated to a fixed point for the
    work-conserving policy's first event — the classic locally-first
    parallelisation of greedy list scheduling, which provably reproduces the
    sequential scan); the work-conserving policy's later events pick their
    starts from the pending lists of the resources freed then;
  - only cores with a completion at the current event time are touched, so
    the merged loop keeps the legacy per-core work complexity.

The legacy per-core schedulers are kept untouched as the *reference oracle*:
``cross_check`` runs both paths and asserts bit-level agreement, and the
differential-testing harness (tests/test_engine_differential.py) drives
randomized instances through it for every algorithm x scheduling policy.
All completion times are computed with the exact float associativity of the
legacy code (``(t + delta) + size/rate``) so agreement is exact, not just
within tolerance.
"""
from __future__ import annotations

import bisect
import dataclasses
import heapq
from typing import TYPE_CHECKING, Annotated, Sequence

import numpy as np

from .assignment import (
    Assignment,
    assign_fast,
    assign_random,
    assign_rho_only,
    assign_tau_aware,
    assignment_from_choices,
)
from .arrays import F8, I8
from .coflow import Coflow, Instance, OnlineInstance, extract_flows
from .effects import effects
from .ordering import order_coflows, priority_scores
from .scheduler import FlowColumns, Schedule
from repro.obs.trace import NULL_TRACER, Tracer, current_tracer

if TYPE_CHECKING:   # runtime import would cycle: fault.py imports engine
    from .fault import FaultApplication, FaultEvent, FaultInjector

__all__ = [
    "FlowTable",
    "FabricState",
    "TickCommit",
    "SCHEDULINGS",
    "INCREMENTAL_SCHEDULINGS",
    "BACKENDS",
    "build_flow_table",
    "schedule_all_cores",
    "run_fast",
    "run_fast_online",
    "run_fast_metrics",
    "cross_check",
    "kernel_divergence",
    "cross_check_online",
    "cross_check_incremental",
]

#: Intra-core policies understood by the engine. ``sunflow`` is the
#: coflow-at-a-time policy used by the SUNFLOW-CORE baselines; the other
#: three mirror ``scheduler.run``'s ``scheduling`` argument.
SCHEDULINGS = ("work-conserving", "priority-guard", "reserving", "sunflow")

#: Assignment-phase backends. ``numpy`` runs the flat-array re-implementation
#: of the Python oracles (bit-identical choices); ``pallas`` dispatches the
#: tau-aware policy to the ``kernels.ops.coflow_assign`` TPU kernel (fp32
#: accumulation — see the precision contract in ``kernels.coflow_assign``);
#: the rho-only and random policies always run the numpy path.
BACKENDS = ("numpy", "pallas")

#: algorithm name -> flat assignment policy.
_POLICY_OF = {
    "ours": "tau-aware",
    "sunflow-core": "tau-aware",
    "rho-assign": "rho-only",
    "rand-assign": "random",
    "rand-sunflow": "random",
}


@dataclasses.dataclass(frozen=True)
class FlowTable:
    """All assigned flows of an instance as flat arrays, in global pi order."""

    pos: Annotated[I8, "F"]   # coflow position in pi
    cid: Annotated[I8, "F"]   # original coflow id
    fi: Annotated[I8, "F"]    # ingress port
    fj: Annotated[I8, "F"]    # egress port
    core: Annotated[I8, "F"]  # assigned core
    size: Annotated[F8, "F"]

    @classmethod
    def from_assignment(cls, assignment: Assignment) -> "FlowTable":
        pos, cid, fi, fj, core, size = [], [], [], [], [], []
        for per_coflow in assignment.flows:
            for af in per_coflow:
                pos.append(af.flow.coflow)
                cid.append(af.flow.cid)
                fi.append(af.flow.i)
                fj.append(af.flow.j)
                core.append(af.core)
                size.append(af.flow.size)
        return cls(
            pos=np.asarray(pos, dtype=np.int64),
            cid=np.asarray(cid, dtype=np.int64),
            fi=np.asarray(fi, dtype=np.int64),
            fj=np.asarray(fj, dtype=np.int64),
            core=np.asarray(core, dtype=np.int64),
            size=np.asarray(size, dtype=np.float64),
        )

    @property
    def n_flows(self) -> int:
        return int(self.pos.size)


def _resolve_algorithm(algorithm: str, scheduling: str) -> tuple[str, str]:
    """(assignment policy, effective scheduling) for an algorithm name."""
    if algorithm not in _POLICY_OF:
        from .scheduler import ALGORITHMS
        raise ValueError(
            f"unknown algorithm {algorithm!r}; one of {sorted(ALGORITHMS)}")
    if algorithm in ("sunflow-core", "rand-sunflow"):
        scheduling = "sunflow"
    return _POLICY_OF[algorithm], scheduling


def _pallas_choices(inst: Instance, flows: tuple[np.ndarray, ...]) -> np.ndarray:
    """Tau-aware choices via the Pallas kernel (fp32 precision contract)."""
    from repro.kernels.ops import coflow_assign

    _pos, _cid, fi, fj, sizes = flows
    out = coflow_assign(fi, fj, sizes, inst.rates, inst.delta, n_ports=inst.N)
    with current_tracer().span("oneshot/assign/fetch"):
        return np.asarray(out, dtype=np.int64)


@effects("rng-consume", "trace-emit")
def build_flow_table(
    inst: Instance,
    pi: Annotated[I8, "M"],
    algorithm: str = "ours",
    *,
    seed: int = 0,
    backend: str = "numpy",
    delta_k: Annotated[F8, "K"] | None = None,
    locality: float = 0.0,
) -> FlowTable:
    """Flat assignment front-end: demand tensors -> assigned ``FlowTable``.

    Runs the vectorized flow extraction (``coflow.extract_flows``) and the
    flat-array assignment policy of ``algorithm`` without building any
    per-flow Python objects. ``backend="pallas"`` dispatches the tau-aware
    policy to the ``kernels.ops.coflow_assign`` TPU kernel (the rho-only and
    random policies have no kernel and always run the numpy path). On the
    numpy backend the resulting core choices are bit-identical to the
    dataclass oracles in ``assignment``.

    ``delta_k`` (a ``(K,)`` per-core reconfiguration-delay vector; fault
    model ``DeltaDrift``) prices the tau-aware completion bounds with each
    core's delay in force instead of the uniform ``inst.delta``. The Pallas
    kernel prices the uniform nominal delta only, so a drifted tau-aware
    assignment always runs the numpy flat state (bit-identical to the
    streaming ``FabricState`` assignment under the same drift); the
    rho-only and random policies never read delta and ignore ``delta_k``.

    ``locality`` (tau-aware only) turns on the fresh-port affinity bias of
    ``assignment.FlatAssignState`` — the kernel knows only the unbiased
    scan, so a locality-biased tau-aware assignment likewise runs the numpy
    flat state regardless of ``backend``.
    """
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")
    if delta_k is not None:
        delta_k = np.asarray(delta_k, dtype=np.float64)
        if delta_k.shape != (inst.K,):
            raise ValueError(
                f"delta_k must have shape ({inst.K},), got {delta_k.shape}")
    policy, _ = _resolve_algorithm(algorithm, "")
    tracer = current_tracer()
    with tracer.span("oneshot/extract") as sp:
        flows = extract_flows(inst, pi)
        if sp.live:
            sp.set(flows=int(flows[0].size))
    drifted = (policy == "tau-aware" and delta_k is not None
               and bool(np.any(delta_k != inst.delta)))  # reprolint: disable=float-eq -- identity check: delta_k entries are copied config/fault values, not arithmetic
    on_kernel = (not drifted and backend == "pallas"
                 and policy == "tau-aware" and not locality)
    with tracer.span("oneshot/assign") as sp:
        if sp.live:
            sp.set(flows=int(flows[0].size),
                   impl="pallas" if on_kernel else "numpy")
        if drifted:
            from .assignment import FlatAssignState

            st = FlatAssignState(policy, inst.rates, inst.delta, inst.N,
                                 seed=seed, locality=locality)
            for k in range(inst.K):
                if delta_k[k] != inst.delta:  # reprolint: disable=float-eq -- identity check: only overridden cores get a set_delta call
                    st.set_delta(k, float(delta_k[k]))
            _pos, _cid, fi, fj, sizes = flows
            core = st.assign(fi, fj, sizes)
        elif on_kernel:
            core = _pallas_choices(inst, flows)
        else:
            core = assign_fast(inst, pi, policy, seed=seed, flows=flows,
                               locality=locality)
    pos, cid, fi, fj, size = flows
    return FlowTable(pos=pos, cid=cid, fi=fi, fj=fj, core=core, size=size)


def _first_occurrence(vals: np.ndarray, scratch: np.ndarray) -> np.ndarray:
    """Boolean mask marking the first occurrence of each value, in order.

    Sort-free: writing positions in reverse leaves each slot of ``scratch``
    holding the *first* position of its value, so a flow is first on its
    resource iff the scratch entry points back at it. ``scratch`` is an
    int64 array of at least ``vals.max() + 1`` entries (contents don't
    matter; only slots touched by ``vals`` are read back).
    """
    n = vals.size
    scratch[vals[::-1]] = np.arange(n - 1, -1, -1)
    return scratch[vals] == np.arange(n)


def _by_resource(flows: np.ndarray, res_ids: np.ndarray, other: np.ndarray,
                 n_res: int) -> tuple[list[list], list[list]]:
    """Per resource, the ``flows`` (ascending) that use it, in priority
    (index) order, and beside them each one's ``other`` resource; both as
    Python lists, sliced from two bulk conversions."""
    order = np.argsort(res_ids, kind="stable")
    ends = np.cumsum(np.bincount(res_ids, minlength=n_res)).tolist()
    fl = flows[order].tolist()
    ol = other[order].tolist()
    starts = [0] + ends[:-1]
    return ([fl[s:e] for s, e in zip(starts, ends)],
            [ol[s:e] for s, e in zip(starts, ends)])


def _scan_pending(others: list, flows: list, free: list, t: float, q: int,
                  rel: list | None) -> tuple[int, int]:
    """Scan one resource's pending list from position ``q`` for its first
    flow that can start at ``t``: its other resource free (``others``), and
    with ``rel`` its release reached. Returns (entries examined, that
    flow's position or -1)."""
    q0 = q
    for o in (others if q == 0 else others[q:]):
        if free[o] <= t and (rel is None or rel[flows[q]] <= t):
            return q - q0 + 1, q
        q += 1
    return q - q0, -1


def _pop_next_event(events: list, t: float) -> float:
    """Earliest completion strictly after t (events is a heapified list)."""
    while events and events[0] <= t:
        heapq.heappop(events)
    if not events:
        raise RuntimeError("scheduler deadlock: pending flows but no events")
    return heapq.heappop(events)


@dataclasses.dataclass
class LoopCounts:
    """What merged event loops did, summed over calls (telemetry only:
    nothing the engine computes reads it back)."""

    #: heap pops, stale entries (times already passed) included
    events: int = 0
    #: pending flows examined at events after the first: by the
    #: work-conserving loop's scans of the freed resources' pending lists
    #: (each entry once an event and list) and of the flows released then;
    #: with ``guard=True``, the pending flows of the cores active at each
    #: event
    candidates: int = 0


def _event_loop(
    rin: np.ndarray,       # (F,) int64 ingress resource ids (core*N + i)
    rout: np.ndarray,      # (F,) int64 egress resource ids (core*N + j)
    srv: np.ndarray,       # (F,) float64 service times size/rate[core]
    core: np.ndarray,      # (F,) int64
    delta: float,
    n_res: int,
    n_ports: int,
    t0: float = 0.0,
    guard: bool = False,
    release: np.ndarray | None = None,
    free_in0: np.ndarray | None = None,
    free_out0: np.ndarray | None = None,
    counts: LoopCounts | None = None,
) -> np.ndarray:
    """Vectorized merged event loop; flows are in priority order per core.

    Returns t_establish per flow. Exactly reproduces the legacy sequential
    scan: at each event, the started set is {flows whose two resources are
    free and which are the first pending user of both} — iterated to a fixed
    point for guard=False (at its first event; later events reach the same
    set by the per-resource picks below), single-pass for guard=True (where
    a pending higher-priority flow makes both its resources unavailable
    whether or not it starts, so "first on both" is already the full
    answer).

    Work-conserving fast path: after each event, every pending flow has at
    least one busy resource (else it would have started), so a flow can
    only become startable at an event where one of its resources completes
    *exactly then*. The first event runs the fixed point over every flow;
    later ones pick their starts per freed resource (:func:`_pick_starts`).
    Each resource keeps the list of its pending flows in priority order (a
    flow leaves both its lists when it starts). For each resource freed at
    ``t`` and still free, its pick is the first flow of its list whose
    other resource is free (and, online, whose release has come); the
    lowest-index pick starts, and the picks are
    renewed, until no resource yields one. That is the sequential scan over
    the flows of the freed resources' lists: a flow of theirs that cannot
    start when the scan reaches it cannot start later at the same event,
    since resources only get busier, so the lowest-index flow that can
    start now is the next one the scan starts; it lies on a freed resource
    that is still free, and no flow before it on that list can start, so it
    is that resource's pick. Per-event work scales with the examined prefix
    of a couple of lists, not with total remaining flows.

    ``release`` (per flow) adds online release gating: a flow is eligible
    only at events ``t >= release[f]`` (exact float comparison, same
    convention as ``circuit_scheduler``). Release times are seeded into the
    event heap, extending the invariant above: a pending flow either has a
    busy resource or an unreached release, so the flows that can start at
    an event lie on just-freed resources or were released exactly then (the
    first of those whose resources are both free is one more pick). An
    unreleased flow never protects its ports under ``guard=True`` (the
    online scheduler cannot know flows that have not arrived).

    ``free_in0``/``free_out0`` (per resource, both or neither) seed the port
    availability horizons from circuits already *committed* by earlier
    service ticks (see ``FabricState``): a resource is busy until its
    horizon, and every horizon value strictly after ``t0`` is seeded into
    the event heap so the loop wakes exactly when a committed circuit tears
    down. ``+inf`` horizons (a failed core's resources, see ``core.fault``)
    are never seeded — no pending flow references them once the fault
    machinery has reassigned its strandlings. With no horizons this is the
    original from-scratch loop.

    ``delta`` is a scalar, or a per-flow ``(F,)`` array when cores have
    drifted reconfiguration delays (``fault.DeltaDrift``); the scalar path
    computes the exact same float expressions as before (a scalar enters
    the per-resource picks as a Python float: exact for a float, an int or
    a float64).

    ``counts``, when given, has this call's heap pops and candidates
    added to it once, at the end (see :class:`LoopCounts`).
    """
    F = rin.size
    t_est = np.full(F, -1.0)
    if F == 0:
        return t_est
    d_vec = None if np.ndim(delta) == 0 else np.asarray(delta, dtype=np.float64)
    if free_in0 is None:
        free_in = np.full(n_res, t0)
        free_out = np.full(n_res, t0)
    else:
        free_in = np.asarray(free_in0, dtype=np.float64).copy()
        free_out = np.asarray(free_out0, dtype=np.float64).copy()
    done = np.zeros(F, dtype=bool)
    scratch = np.empty(n_res, dtype=np.int64)
    events: list = []  # heap of future completion (and release) times
    if free_in0 is not None:
        seed_in = free_in[(free_in > t0) & np.isfinite(free_in)]
        seed_out = free_out[(free_out > t0) & np.isfinite(free_out)]
        events = np.unique(np.concatenate([seed_in, seed_out])).tolist()
    remaining = F
    t = t0
    if release is not None:
        rel_uniq, rel_inv = np.unique(release, return_inverse=True)
        events.extend(rel_uniq.tolist())
        heapq.heapify(events)
    # every flow pushes one completion, so the pops are the pushes less
    # what is left in the heap at the end
    n_pushed = len(events) + F
    n_cand = 0

    if guard:
        pending = np.arange(F)
        first_event = True
        while remaining:
            if first_event:
                pend = pending
                first_event = False
            else:
                # Only cores with a completion (or a release) at t can
                # start flows now.
                act = np.zeros(n_res // n_ports, dtype=bool)
                act[np.nonzero(free_in == t)[0] // n_ports] = True  # reprolint: disable=float-eq -- exact-float convention: t was copied verbatim from free_in (circuit_scheduler docstring)
                act[np.nonzero(free_out == t)[0] // n_ports] = True  # reprolint: disable=float-eq -- exact-float convention: t was copied verbatim from free_out
                if release is not None:
                    act[core[pending[release[pending] == t]]] = True  # reprolint: disable=float-eq -- exact-float convention: event times are copied release values, never arithmetic
                pend = pending[act[core[pending]]]
                n_cand += pend.size
            if release is not None and pend.size:
                pend = pend[release[pend] <= t]
            if pend.size:
                ri, rj = rin[pend], rout[pend]
                feas = (
                    (free_in[ri] <= t) & (free_out[rj] <= t)
                    & _first_occurrence(ri, scratch) & _first_occurrence(rj, scratch)
                )
                start = pend[feas]
                if start.size:
                    tc = (t + (delta if d_vec is None else d_vec[start])) \
                        + srv[start]
                    free_in[rin[start]] = tc
                    free_out[rout[start]] = tc
                    t_est[start] = t
                    done[start] = True
                    remaining -= start.size
                    for v in tc.tolist():
                        heapq.heappush(events, v)
                    pending = pending[~done[pending]]
                    if not remaining:
                        break
            t = _pop_next_event(events, t)
        if counts is not None:
            counts.events += n_pushed - len(events)
            counts.candidates += n_cand
        return t_est

    # The first event: every (released) flow is a candidate.
    cand = np.arange(F)
    if release is not None:
        cand = cand[release[cand] <= t]
    cand = cand[(free_in[rin[cand]] <= t) & (free_out[rout[cand]] <= t)]
    while cand.size:
        safe = _first_occurrence(rin[cand], scratch) \
            & _first_occurrence(rout[cand], scratch)
        start = cand[safe]
        tc = (t + (delta if d_vec is None else d_vec[start])) + srv[start]
        free_in[rin[start]] = tc
        free_out[rout[start]] = tc
        t_est[start] = t
        done[start] = True
        remaining -= start.size
        for v in tc.tolist():
            heapq.heappush(events, v)
        cand = cand[~safe]
        cand = cand[(free_in[rin[cand]] <= t) & (free_out[rout[cand]] <= t)]
    if remaining:
        t_est, n_cand = _pick_starts(rin, rout, srv, delta, n_res, t, events,
                                     free_in, free_out, t_est, done,
                                     remaining, release)
    if counts is not None:
        counts.events += n_pushed - len(events)
        counts.candidates += n_cand
    return t_est


def _pick_starts(
    rin: np.ndarray, rout: np.ndarray, srv: np.ndarray,
    delta: float | np.ndarray, n_res: int, t: float, events: list,
    free_in: np.ndarray, free_out: np.ndarray, t_est: np.ndarray,
    done: np.ndarray, remaining: int, release: np.ndarray | None,
) -> tuple[np.ndarray, int]:
    """The work-conserving loop's events after the first (``t``), by the
    per-freed-resource pick that :func:`_event_loop` describes.

    Ingress resource ``r`` is ``r`` here, egress resource ``r`` is
    ``n_res + r``; state lives in Python lists, since an event touches a
    handful of scalars. A dict from completion time to the resources it
    frees (seeded from the horizons still ahead of ``t``) stands in for
    scanning every resource for ``free == t``; ``events`` keeps one heap
    entry per completion, so its pops are counted as before. Returns
    ``t_est`` and the entries the scans examined.
    """
    pend = np.flatnonzero(~done)
    lists, others = _by_resource(
        np.concatenate([pend, pend]),
        np.concatenate([rin[pend], rout[pend] + n_res]),
        np.concatenate([rout[pend] + n_res, rin[pend]]), 2 * n_res)
    A = rin.tolist()
    B = (rout + n_res).tolist()
    free_all = np.concatenate([free_in, free_out])
    free = free_all.tolist()
    freeing: dict[float, list] = {}
    ahead = np.flatnonzero((free_all > t) & np.isfinite(free_all))
    for r, v in zip(ahead.tolist(), free_all[ahead].tolist()):
        freeing.setdefault(v, []).append(r)
    te = t_est.tolist()
    started = done.tolist()
    sv = srv.tolist()
    if np.ndim(delta) == 0:
        delta, d_l = float(delta), None
    else:
        d_l = np.asarray(delta, dtype=np.float64).tolist()
    rel = rel_map = None
    if release is not None:
        rel = release.tolist()
        # flow indices grouped by release value, in priority order
        rel_map = {}
        for f in np.argsort(release, kind="stable").tolist():
            rel_map.setdefault(rel[f], []).append(f)
    scan = _scan_pending
    n_cand = 0
    while remaining:
        t = _pop_next_event(events, t)
        # picks: (flow, resource or -1 for the flows released at t, the
        # flow's position in that list)
        picks = []
        for r in freeing.pop(t, ()):
            n, q = scan(others[r], lists[r], free, t, 0, rel)
            n_cand += n
            if q >= 0:
                picks.append((lists[r][q], r, q))
        rel_now = None if rel_map is None else rel_map.get(t)
        if rel_now is not None:
            # this scan always runs to the end of its list within the event
            n_cand += len(rel_now)
            for q, f in enumerate(rel_now):
                if free[A[f]] <= t and free[B[f]] <= t:
                    picks.append((f, -1, q))
                    break
        while picks:
            f = min(picks)[0]
            tc = (t + (delta if d_l is None else d_l[f])) + sv[f]
            a, b = A[f], B[f]
            free[a] = tc
            free[b] = tc
            te[f] = t
            started[f] = True
            remaining -= 1
            heapq.heappush(events, tc)
            frees = freeing.get(tc)
            if frees is None:
                freeing[tc] = [a, b]
            else:
                frees += (a, b)
            for r in (a, b):
                q = bisect.bisect_left(lists[r], f)
                del lists[r][q], others[r][q]
            # renew the picks: a busy resource has none; a pick that can
            # still start stands; else scan on from after it
            renewed = []
            for g, r, q in picks:
                if r >= 0:
                    if free[r] > t:
                        continue
                    if g != f and free[others[r][q]] <= t:
                        renewed.append((g, r, q))
                        continue
                    n, q = scan(others[r], lists[r], free, t,
                                q if g == f else q + 1, rel)
                    n_cand += n
                    if q >= 0:
                        renewed.append((lists[r][q], r, q))
                else:
                    for q in range(q, len(rel_now)):
                        h = rel_now[q]
                        if (not started[h] and free[A[h]] <= t
                                and free[B[h]] <= t):
                            renewed.append((h, -1, q))
                            break
            picks = renewed
    return np.array(te), n_cand


def _reserving_times(
    rin: np.ndarray, rout: np.ndarray, srv: np.ndarray, delta: float,
    n_res: int, release: np.ndarray | None = None,
    avail_in: np.ndarray | None = None,
    avail_out: np.ndarray | None = None,
) -> np.ndarray:
    """Strict in-order reservation (no backfill) over merged resources.

    ``release`` (per flow) is the online variant: flows are given in
    commitment (arrival) order and each reservation starts no earlier than
    its release.

    ``avail_in``/``avail_out`` (both or neither) carry reservation horizons
    across service ticks; they are MUTATED in place, which is exactly the
    incremental contract — a reservation, once made, never changes, so the
    arrays double as the committed-circuit state.

    ``delta`` may be a per-flow ``(F,)`` array (drifted per-core delays).
    """
    d_vec = None if np.ndim(delta) == 0 else np.asarray(delta, dtype=np.float64)
    if avail_in is None:
        avail_in = np.zeros(n_res)
        avail_out = np.zeros(n_res)
    t_est = np.empty(rin.size)
    for f in range(rin.size):
        i, j = rin[f], rout[f]
        t = avail_in[i] if avail_in[i] >= avail_out[j] else avail_out[j]
        if release is not None and release[f] > t:
            t = release[f]
        tc = t + (delta if d_vec is None else d_vec[f]) + srv[f]
        avail_in[i] = tc
        avail_out[j] = tc
        t_est[f] = t
    return t_est


def _sunflow_times(
    table: FlowTable,
    rin: np.ndarray,
    rout: np.ndarray,
    srv: np.ndarray,
    delta: float,
    n_ports: int,
    K: int,
    release: np.ndarray | None = None,
    prio: np.ndarray | None = None,
    delta_k: Annotated[F8, "K"] | None = None,
    counts: LoopCounts | None = None,
) -> np.ndarray:
    """SUNFLOW-CORE: per core, coflows strictly sequential (barrier), flows of
    one coflow scheduled largest-first.

    The legacy ``schedule_core_sunflow`` runs ``_run_list_scheduler`` with the
    priority-guarded scan — reproduced here with ``guard=True``.

    ``release``/``prio`` (per flow; all flows of a coflow share both) select
    the online variant: whenever the core frees, the *arrived* unserved
    coflow with the best priority rank is served next, idling until the next
    arrival if none is pending (matching ``online._sunflow_core_online``).

    ``delta_k`` (per-core drifted delays) replaces the scalar ``delta``
    core by core; the undrifted path computes the same floats as before.
    """
    t_est = np.full(table.n_flows, -1.0)
    idx = np.arange(table.n_flows)
    for k in range(K):
        dk = delta if delta_k is None else float(delta_k[k])
        on_k = idx[table.core == k]
        barrier = 0.0
        if release is None:
            # groups in pi order; intra-group largest-first with (i, j)
            # tie-break, matching schedule_core_sunflow exactly.
            serve_order = list(np.unique(table.pos[on_k]))
        else:
            serve_order = None
            rel_of = {int(table.pos[f]): float(release[f]) for f in on_k}
            prio_of = {int(table.pos[f]): int(prio[f]) for f in on_k}
            # insertion-ordered dict, not a set: the ready-list scan below
            # must iterate in a deterministic order (reprolint RL104)
            unserved = dict.fromkeys(rel_of)
        while True:
            if release is None:
                if not serve_order:
                    break
                pos = serve_order.pop(0)
            else:
                if not unserved:
                    break
                ready = [p for p in unserved if rel_of[p] <= barrier]
                if not ready:
                    barrier = min(rel_of[p] for p in unserved)
                    ready = [p for p in unserved if rel_of[p] <= barrier]
                pos = min(ready, key=lambda p: prio_of[p])
                del unserved[pos]
            grp = on_k[table.pos[on_k] == pos]
            order = np.lexsort((table.fj[grp], table.fi[grp], -table.size[grp]))
            grp = grp[order]
            te = _event_loop(
                rin[grp], rout[grp], srv[grp], table.core[grp], dk,
                n_res=K * n_ports, n_ports=n_ports, t0=barrier, guard=True,
                counts=counts,
            )
            t_est[grp] = te
            barrier = max(barrier, float(((te + dk) + srv[grp]).max()))
    return t_est


@effects("trace-emit")
def _times_for_table(
    inst: Instance,
    pi: np.ndarray,
    table: FlowTable,
    scheduling: str = "work-conserving",
    releases: Annotated[F8, "M"] | None = None,
    delta_k: Annotated[F8, "K"] | None = None,
) -> tuple[np.ndarray, np.ndarray]:
    """Scheduling phase over a flat ``FlowTable``: returns (t_est, srv).

    ``releases`` (indexed by ORIGINAL coflow id, like
    ``OnlineInstance.releases``) switches on the online model: scheduling
    priority becomes the WSPT rank of each coflow (``online.online_orders``),
    eligibility is release-gated in the merged event loop, and the sunflow /
    reserving policies use their online variants. ``releases=None`` is the
    offline path.

    ``delta_k`` (per-core drifted delays; fault model ``DeltaDrift``)
    replaces the uniform ``inst.delta`` with ``delta_k[core]`` per flow.
    ``None`` (or an all-nominal vector, which callers should normalize to
    ``None``) computes the exact pre-drift floats.

    Traced as ``oneshot/event_loop``, with the event loops' ``events`` and
    ``candidates`` (:class:`LoopCounts`) unless the policy is reserving.
    """
    with current_tracer().span("oneshot/event_loop") as sp:
        counts = LoopCounts() if sp.live else None
        K, N = inst.K, inst.N
        rin = table.core * N + table.fi
        rout = table.core * N + table.fj
        srv = table.size / inst.rates[table.core]
        dl = inst.delta if delta_k is None \
            else np.asarray(delta_k, dtype=np.float64)[table.core]
        if scheduling not in SCHEDULINGS:
            raise ValueError(
                f"unknown scheduling {scheduling!r}; one of {SCHEDULINGS}")
        if releases is None:
            if scheduling == "work-conserving":
                t_est = _event_loop(rin, rout, srv, table.core, dl, K * N, N,
                                    counts=counts)
            elif scheduling == "priority-guard":
                t_est = _event_loop(rin, rout, srv, table.core, dl, K * N, N,
                                    guard=True, counts=counts)
            elif scheduling == "reserving":
                t_est = _reserving_times(rin, rout, srv, dl, K * N)
            elif scheduling == "sunflow":
                t_est = _sunflow_times(table, rin, rout, srv, inst.delta, N, K,
                                       delta_k=delta_k, counts=counts)
        else:
            from .online import online_orders

            rel_orig = np.asarray(releases, dtype=np.float64)
            orig = np.asarray(pi)[table.pos]
            rel_f = rel_orig[orig]
            _, prio_rank = online_orders(inst, rel_orig)
            prio_f = prio_rank[orig]
            if scheduling in ("work-conserving", "priority-guard"):
                # The event loop wants flows in scheduling-priority order: WSPT
                # coflow rank, then the intra-coflow assignment order (stable).
                perm = np.argsort(prio_f, kind="stable")
                te = _event_loop(
                    rin[perm], rout[perm], srv[perm], table.core[perm],
                    dl if delta_k is None else dl[perm], K * N, N,
                    guard=(scheduling == "priority-guard"),
                    release=rel_f[perm], counts=counts)
                t_est = np.empty_like(te)
                t_est[perm] = te
            elif scheduling == "reserving":
                # commitment in arrival order == the FlowTable's native order
                t_est = _reserving_times(rin, rout, srv, dl, K * N,
                                         release=rel_f)
            elif scheduling == "sunflow":
                t_est = _sunflow_times(table, rin, rout, srv, inst.delta, N, K,
                                       release=rel_f, prio=prio_f,
                                       delta_k=delta_k, counts=counts)
        if counts is not None and scheduling != "reserving":
            sp.set(events=counts.events, candidates=counts.candidates)
    return t_est, srv


def _ccts_from_times(inst: Instance, pi: np.ndarray, table: FlowTable,
                     t_est: np.ndarray, srv: np.ndarray,
                     delta_f: np.ndarray | None = None) -> np.ndarray:
    """Per-coflow CCTs (original id order) straight from the flat arrays.

    ``delta_f`` is the per-flow reconfiguration delay in force (drifted
    cores); ``None`` is the uniform ``inst.delta`` with the exact pre-drift
    float expression."""
    ccts = np.zeros(inst.M)
    t_complete = (t_est + (inst.delta if delta_f is None else delta_f)) + srv
    np.maximum.at(ccts, np.asarray(pi)[table.pos], t_complete)
    return ccts


@effects("trace-emit")
def _schedule_from_times(
    inst: Instance,
    pi: np.ndarray,
    assignment: Assignment | None,
    table: FlowTable,
    t_est: np.ndarray,
    srv: np.ndarray,
    delta_f: np.ndarray | None = None,
) -> Schedule:
    """The schedule's flows as columns in the legacy order: core-major,
    priority order within each core (schedule_core_sunflow emits coflow
    groups in pi order too, so core-major pi order matches it as well).
    Start and completion times are the per-flow record's float expressions,
    ``(t_est + delta) + size / rate``, elementwise. No ``ScheduledFlow`` is
    built here; ``Schedule.flows`` builds them if a caller reads it.
    Traced as ``oneshot/schedule``."""
    with current_tracer().span("oneshot/schedule"):
        order = np.lexsort((np.arange(table.n_flows), table.core))
        core = table.core[order]
        size = table.size[order]
        t_e = np.asarray(t_est, dtype=np.float64)[order]
        t_start = t_e + (inst.delta if delta_f is None else delta_f[order])
        columns = FlowColumns(
            coflow=table.pos[order], cid=table.cid[order],
            i=table.fi[order], j=table.fj[order], core=core, size=size,
            t_establish=t_e, t_start=t_start,
            t_complete=t_start + size / inst.rates[core])
        ccts = _ccts_from_times(inst, pi, table, t_est, srv, delta_f)
    return Schedule(inst=inst, pi=pi, assignment=assignment, ccts=ccts,
                    columns=columns)


def schedule_all_cores(
    inst: Instance,
    pi: Annotated[I8, "M"],
    assignment: Assignment,
    scheduling: str = "work-conserving",
    *,
    releases: Annotated[F8, "M"] | None = None,
) -> Schedule:
    """Schedule every assigned flow on all K cores in one vectorized call.

    Drop-in replacement for ``scheduler._schedule_from_assignment``; produces
    identical ``Schedule`` contents (flows in core-major priority order, same
    establishment times bit-for-bit). See ``_times_for_table`` for the online
    (``releases``) semantics. The flat production path (``run_fast`` /
    ``run_fast_metrics``) skips this object front-end entirely and schedules
    a ``FlowTable`` built by ``build_flow_table``.
    """
    table = FlowTable.from_assignment(assignment)
    t_est, srv = _times_for_table(inst, pi, table, scheduling, releases)
    return _schedule_from_times(inst, pi, assignment, table, t_est, srv)


def _normalize_delta_k(inst: Instance,
                       delta_k: np.ndarray | None) -> np.ndarray | None:
    """Validate a per-core delay vector; an all-nominal vector becomes
    ``None`` so the undrifted pipeline keeps its exact scalar float
    expressions (drift-to-nominal round trips are bit-identical)."""
    if delta_k is None:
        return None
    delta_k = np.asarray(delta_k, dtype=np.float64)
    if delta_k.shape != (inst.K,):
        raise ValueError(
            f"delta_k must have shape ({inst.K},), got {delta_k.shape}")
    if (delta_k < 0).any():
        raise ValueError("drifted delta must be >= 0")
    if np.all(delta_k == inst.delta):
        return None
    return delta_k


@effects("rng-consume", "trace-emit")
def run_fast(
    inst: Instance,
    algorithm: str = "ours",
    *,
    seed: int = 0,
    scheduling: str = "work-conserving",
    backend: str = "numpy",
    delta_k: Annotated[F8, "K"] | None = None,
    locality: float = 0.0,
) -> Schedule:
    """Batched-engine counterpart of ``scheduler.run`` (same semantics).

    The whole pipeline is flat arrays until the returned ``Schedule`` is
    materialized: vectorized extraction + flat assignment
    (``build_flow_table``) feed the vectorized scheduling engine directly —
    no ``Flow``/``AssignedFlow`` objects are built (the returned schedule's
    ``assignment`` is ``None``; the legacy object path remains the oracle).
    On ``backend="numpy"`` the result is bit-identical to ``scheduler.run``
    (which is what ``cross_check`` and the differential suites assert);
    ``backend="pallas"`` runs tau-aware assignment on the TPU kernel (fp32
    precision contract — see ``kernels.coflow_assign``).

    ``delta_k`` (per-core drifted reconfiguration delays; fault model
    ``DeltaDrift``) prices assignment and scheduling with each core's delay
    in force — what the one-shot service plane passes when the fabric has
    drifted. ``None`` (or all-nominal) is the exact pre-drift pipeline.
    ``locality`` (tau-aware only) is the fresh-port affinity bias — it
    changes core choices, so the result is gated by the referee and wCCT
    comparisons, not bit-exactness (see DESIGN.md §Delta-scheduling).
    """
    delta_k = _normalize_delta_k(inst, delta_k)
    with current_tracer().span("oneshot/order"):
        pi = order_coflows(inst)
    _, scheduling = _resolve_algorithm(algorithm, scheduling)
    table = build_flow_table(inst, pi, algorithm, seed=seed, backend=backend,
                             delta_k=delta_k, locality=locality)
    t_est, srv = _times_for_table(inst, pi, table, scheduling,
                                  delta_k=delta_k)
    dl_f = None if delta_k is None else delta_k[table.core]
    return _schedule_from_times(inst, pi, None, table, t_est, srv, dl_f)


@effects("rng-consume", "trace-emit")
def run_fast_metrics(
    inst: Instance,
    algorithm: str = "ours",
    *,
    seed: int = 0,
    scheduling: str = "work-conserving",
    backend: str = "numpy",
    releases: Annotated[F8, "M"] | None = None,
    delta_k: Annotated[F8, "K"] | None = None,
    locality: float = 0.0,
) -> tuple[np.ndarray, int]:
    """Metrics-only fast path: per-coflow CCTs without object materialization.

    Same pipeline as ``run_fast`` / ``run_fast_online`` (identical CCTs, per
    the differential suite) but stops at the flat arrays: no ``Schedule``, no
    ``ScheduledFlow`` or ``Assignment`` objects. Returns ``(ccts, n_flows)``
    with ``ccts`` indexed by original coflow id — all ``SweepRow`` metrics
    derive from these, which is what ``run_batch(materialize="metrics")``
    consumes at trace scale.
    """
    with current_tracer().span("oneshot/order"):
        if releases is None:
            pi = order_coflows(inst)
        else:
            from .online import online_orders

            releases = np.asarray(releases, dtype=np.float64)
            pi, _ = online_orders(inst, releases)
    delta_k = _normalize_delta_k(inst, delta_k)
    _, scheduling = _resolve_algorithm(algorithm, scheduling)
    table = build_flow_table(inst, pi, algorithm, seed=seed, backend=backend,
                             delta_k=delta_k, locality=locality)
    t_est, srv = _times_for_table(inst, pi, table, scheduling, releases,
                                  delta_k=delta_k)
    dl_f = None if delta_k is None else delta_k[table.core]
    return _ccts_from_times(inst, pi, table, t_est, srv, dl_f), table.n_flows


@effects("rng-consume", "trace-emit")
def run_fast_online(
    oinst: OnlineInstance,
    algorithm: str = "ours",
    *,
    seed: int = 0,
    scheduling: str = "work-conserving",
    backend: str = "numpy",
    delta_k: Annotated[F8, "K"] | None = None,
    locality: float = 0.0,
) -> Schedule:
    """Batched-engine counterpart of ``online.run_online`` (same semantics).

    The flat pipeline of ``run_fast`` with the arrival order in place of the
    offline pi: per-arrival irrevocable assignment is the same greedy rule
    over the same flow order, so the flat choices are bit-identical to the
    oracle's ``_assign_at_arrival``; the release-gated scheduling phase goes
    through the vectorized engine (``cross_check_online`` and
    tests/test_online_differential.py assert agreement with ``run_online``).
    With ``releases == 0`` the result is bit-identical to the offline
    ``run_fast``. ``delta_k`` prices drifted per-core delays exactly as in
    ``run_fast``.
    """
    inst = oinst.inst
    rel = np.asarray(oinst.releases, dtype=np.float64)
    from .online import online_orders

    delta_k = _normalize_delta_k(inst, delta_k)
    with current_tracer().span("oneshot/order"):
        arrival, _ = online_orders(inst, rel)
    _, scheduling = _resolve_algorithm(algorithm, scheduling)
    table = build_flow_table(inst, arrival, algorithm, seed=seed,
                             backend=backend, delta_k=delta_k,
                             locality=locality)
    t_est, srv = _times_for_table(inst, arrival, table, scheduling,
                                  releases=rel, delta_k=delta_k)
    dl_f = None if delta_k is None else delta_k[table.core]
    return _schedule_from_times(inst, arrival, None, table, t_est, srv, dl_f)


# --------------------------------------------------------------------------
# Incremental (streaming) scheduling: the fabric-manager entry point.
#
# ``FabricState`` carries committed per-core port-availability horizons and
# the persistent assignment-phase state across service ticks, so each tick
# schedules only the *pending* flows (new arrivals + not-yet-committed
# leftovers) against the circuits already programmed — instead of replaying
# the whole arrival history through ``run_fast_online``.
#
# Bit-exactness vs the full replay rests on the commit rule: a circuit is
# committed at tick time T iff its establishment time is <= T. Release
# gating is the exact comparison ``release <= t``, and every coflow admitted
# after tick T must have release > T, so no future arrival can participate
# in (or, under ``priority-guard``, protect ports at) any event at or before
# T — the committed prefix of the schedule is final. Everything later stays
# tentative and is re-derived next tick with the newly arrived competitors,
# which is exactly what the full replay's event loop would do.
# --------------------------------------------------------------------------

#: Intra-core policies the incremental path supports. The sunflow baselines
#: pick the next coflow at core-free time — a decision that arrivals *after*
#: the current tick can overturn (the pick may happen arbitrarily far in the
#: future), so they cannot commit tick-by-tick and require full replay.
INCREMENTAL_SCHEDULINGS = ("work-conserving", "priority-guard", "reserving")

_PEND_FIELDS = (
    ("gid", np.int64), ("cid", np.int64), ("fi", np.int64), ("fj", np.int64),
    ("core", np.int64), ("size", np.float64), ("srv", np.float64),
    ("rel", np.float64), ("score", np.float64), ("intra", np.int64),
)

#: Committed-circuit retention (``track_commits``): the pending fields plus
#: the committed times (what fault classification and horizon rebuilds
#: read; the delay in force reaches programs via ``TickCommit.delta_f``).
_COMMIT_FIELDS = _PEND_FIELDS + (
    ("t_est", np.float64), ("t_comp", np.float64),
)


def _resource_components(rin: np.ndarray, rout: np.ndarray,
                         n_res: int) -> np.ndarray:
    """Per-row component labels of the bipartite resource-sharing graph.

    Flows interact ONLY through shared (core, port) resources — the event
    loop starts a flow by comparing it against the other users of its two
    resources, and nothing else. So the pending set decomposes exactly into
    connected components of the bipartite graph over ingress resources and
    egress resources (offset by ``n_res``), one edge per flow. Returns, for
    each row, the union-find root of its ingress resource — rows share a
    label iff they are in the same component (the row's egress resource is
    always unioned with its ingress, so either endpoint labels it).

    Union-find over the ``2 * n_res`` resource nodes with one union per
    *distinct* resource pair — O(unique pairs + n_res), independent of the
    backlog's flow count.
    """
    span = 2 * n_res
    pairs = np.unique(rin * span + (rout + n_res))
    parent = list(range(span))

    def find(x: int) -> int:
        root = x
        while parent[root] != root:
            root = parent[root]
        while parent[x] != root:
            parent[x], x = root, parent[x]
        return root

    for p in pairs.tolist():
        a, b = find(p // span), find(p % span)
        if a != b:
            parent[b] = a
    root_of = np.fromiter((find(r) for r in range(n_res)),
                          dtype=np.int64, count=n_res)
    return root_of[rin]


def _touched_rows(rin: np.ndarray, rout: np.ndarray, n_res: int,
                  n_new_from: int) -> np.ndarray:
    """Delta-scheduling touched set: which pending rows a new arrival can
    perturb.

    A batch of new rows (indices ``>= n_new_from``) can only change the
    tentative times of rows in resource components it touches:
    cross-component flows share no resource with any new flow, directly or
    transitively, so every availability horizon and first-pending-candidate
    test they see is unchanged (the not-all-stop property of the OCS model,
    applied to scheduling work instead of circuits). Returns a boolean row
    mask over the components of ``_resource_components``.
    """
    F = rin.size
    if n_new_from <= 0:
        return np.ones(F, dtype=bool)
    if n_new_from >= F:
        return np.zeros(F, dtype=bool)
    roots = _resource_components(rin, rout, n_res)
    return np.isin(roots, roots[n_new_from:])


class ComponentIndex:
    """Incremental resource-component index over the pending set.

    Maintains the union-find of ``_resource_components`` ACROSS ticks
    instead of rebuilding it from every pending row each tick: the pending
    set changes by small deltas (an arrival batch in, committed rows out,
    fault strand/requeue churn), so the index tracks the multiset of
    distinct ``(rin, rout)`` resource pairs and updates the union-find only
    for pairs entering or leaving. ``labels()`` then answers the per-tick
    component query in one vectorized pointer-jumping pass — replacing the
    two from-scratch union-finds (``_touched_rows`` + the telemetry call)
    the splice used to pay per tick, each O(F log F) in the backlog size.

    Exactness contract (differentially pinned in
    ``tests/test_component_index.py``, and end-to-end by the delta-vs-full
    twin drives): after any add/remove sequence, ``labels()`` induces the
    SAME PARTITION of the pending rows as the from-scratch oracle
    ``_resource_components`` on the same rows. Raw label values may differ
    while the index is ahead of its last rebuild (union order differs from
    the oracle's sorted-pair order), but every consumer — the touched-row
    mask ``isin(roots, roots[seed])``, the component counts, the size
    histograms — is a partition function, so all computed schedules and
    telemetry are bit-identical either way. Removing the last copy of a
    pair can SPLIT a component, which a union-find cannot express
    incrementally; the index marks itself dirty and the next ``labels()``
    call rebuilds from the surviving pairs in sorted order (exactly the
    oracle's procedure — after a rebuild even the raw labels match).

    Mutation ownership: the internal arrays (``_parent``, the pair multiset)
    are committed scheduling state and MUST only be mutated here in
    ``core/engine.py`` — reprolint RL106 enforces this statically, exactly
    as for ``FlowTable`` / ``FlatAssignState``.
    """

    __slots__ = ("n_res", "span", "_count", "_parent", "_dirty")

    def __init__(self, n_res: int) -> None:
        self.n_res = int(n_res)
        #: node ids: ingress resource r -> r, egress resource r -> r + n_res
        self.span = 2 * self.n_res
        #: pair-key multiset: rin * span + (rout + n_res) -> multiplicity
        self._count: dict[int, int] = {}
        self._parent = np.arange(self.span, dtype=np.int64)
        self._dirty = False

    @property
    def n_pairs(self) -> int:
        """Distinct resource pairs currently present."""
        return len(self._count)

    def _find(self, x: int) -> int:
        parent = self._parent
        root = x
        while parent[root] != root:
            root = int(parent[root])
        while parent[x] != root:
            parent[x], x = root, int(parent[x])
        return root

    def add(self, rin: Annotated[I8, "B"],
            rout: Annotated[I8, "B"]) -> None:
        """Pending rows entered (arrival batch / fault requeue)."""
        count = self._count
        span, n_res = self.span, self.n_res
        for a, b in zip(rin.tolist(), rout.tolist()):
            b += n_res
            key = a * span + b
            c = count.get(key)
            if c:
                count[key] = c + 1
            else:
                count[key] = 1
                ra, rb = self._find(a), self._find(b)
                if ra != rb:
                    self._parent[rb] = ra

    def remove(self, rin: Annotated[I8, "B"],
               rout: Annotated[I8, "B"]) -> None:
        """Pending rows left (commit / fault strand).

        Dropping the last copy of a pair may split its component; the
        union-find can only merge, so the index goes dirty and the next
        ``labels()`` rebuilds from the surviving pairs.
        """
        count = self._count
        span, n_res = self.span, self.n_res
        for a, b in zip(rin.tolist(), rout.tolist()):
            key = a * span + (b + n_res)
            c = count[key] - 1
            if c:
                count[key] = c
            else:
                del count[key]
                self._dirty = True

    def _rebuild(self) -> None:
        """From-scratch union over the surviving pairs, in sorted-key order
        — the oracle's exact procedure (``_resource_components``), so the
        rebuilt parent forest is identical to a fresh one."""
        self._parent = np.arange(self.span, dtype=np.int64)
        span = self.span
        for key in sorted(self._count):
            a, b = self._find(key // span), self._find(key % span)
            if a != b:
                self._parent[b] = a
        self._dirty = False

    def labels(self, nodes: Annotated[I8, "Q"]) -> Annotated[I8, "Q"]:
        """Component label per node id (use ``labels(rin)`` for row labels,
        matching the oracle's ingress-root convention; egress nodes are
        ``r + n_res``). Vectorized pointer jumping — terminates because the
        parent forest is acyclic with self-loop roots."""
        if self._dirty:
            self._rebuild()
        parent = self._parent
        lab = parent[nodes]
        while True:
            nxt = parent[lab]
            if np.array_equal(nxt, lab):
                return lab
            lab = nxt


@dataclasses.dataclass(frozen=True)
class TickCommit:
    """Circuits committed by one ``FabricState`` tick, as flat arrays.

    ``gid`` is the stream-wide admission index of the flow's coflow (the
    service's coflow identity); ``cid`` echoes the submitted ``Coflow.cid``.
    ``finalized`` lists the coflows whose last flow committed this tick as
    ``(gid, cid, cct, weight)`` tuples — their CCT is now final.

    ``delta_f`` is the per-flow reconfiguration delay in force at commit
    time (``None`` = the fabric's uniform nominal delta; an array only after
    a ``fault.DeltaDrift``). ``faults`` lists the ``FaultApplication``
    records of injector events applied at this tick, and ``unfinalized``
    the gids whose previously reported final CCT those faults retracted.
    """

    t_now: float
    gid: Annotated[I8, "Fc"]
    cid: Annotated[I8, "Fc"]
    fi: Annotated[I8, "Fc"]
    fj: Annotated[I8, "Fc"]
    core: Annotated[I8, "Fc"]
    size: Annotated[F8, "Fc"]
    t_establish: Annotated[F8, "Fc"]
    t_complete: Annotated[F8, "Fc"]
    finalized: tuple         # ((gid, cid, cct, weight), ...)
    n_pending: int           # flows still tentative after this tick
    delta_f: Annotated[F8, "Fc"] | None = None  # set after a DeltaDrift
    faults: tuple = ()       # (FaultApplication, ...) applied this tick
    unfinalized: tuple = ()  # gids whose final CCT was retracted this tick
    #: resource-sharing components in this tick's pending set, and how many
    #: of them the tick actually re-scheduled (delta-scheduling telemetry;
    #: both 0 when delta-scheduling is off, reserving, or nothing pends)
    components_total: int = 0
    components_touched: int = 0

    @property
    def n_flows(self) -> int:
        return int(self.gid.size)


class FabricState:
    """Incremental online-scheduling state carried across service ticks.

    Usage: one ``step(coflows, releases, t_now)`` call per service tick.
    Admission contract (checked): tick times are non-decreasing, and every
    release lies in ``(previous tick time, t_now]`` — i.e. arrivals are
    admitted at the first tick at or after their release. ``finalize()``
    commits everything still pending (the end-of-stream tick at t=inf).

    The committed circuits across all ticks are bit-identical — same core
    choices, same establishment times — to one ``run_fast_online`` call over
    the whole stream (coflows indexed in admission order), which
    ``cross_check_incremental`` asserts and tests/test_service.py fuzzes.
    """

    def __init__(
        self,
        *,
        rates: Annotated[F8, "K"],
        delta: float,
        N: int,
        algorithm: str = "ours",
        scheduling: str = "work-conserving",
        seed: int = 0,
        faults: "FaultInjector | None" = None,
        track_commits: bool | None = None,
        delta_schedule: bool = True,
        fault_lookback: float = np.inf,
        tracer: Tracer | None = None,
        locality: float = 0.0,
    ) -> None:
        policy, scheduling = _resolve_algorithm(algorithm, scheduling)
        if scheduling not in INCREMENTAL_SCHEDULINGS:
            raise ValueError(
                f"scheduling {scheduling!r} (algorithm {algorithm!r}) is "
                f"benchmark-only: the sunflow pick-next-at-core-free rule "
                f"cannot commit tick-by-tick and requires a full "
                f"run_fast_online replay (serve it via run_fast / "
                f"run_fast_online / run_batch); incremental scheduling "
                f"supports {INCREMENTAL_SCHEDULINGS}")
        self.rates = np.asarray(rates, dtype=np.float64)
        if self.rates.ndim != 1 or (self.rates <= 0).any():
            raise ValueError("rates must be a 1-D positive vector")
        self.delta = float(delta)
        self.N = int(N)
        self.K = int(self.rates.shape[0])
        self.R = float(self.rates.sum())
        self.algorithm = algorithm
        self.scheduling = scheduling
        #: phase tracer (repro.obs): purely observational — nothing the
        #: engine computes ever reads it, so NULL_TRACER (the default) and
        #: a recording tracer yield bit-identical schedules
        self._tracer: Tracer = NULL_TRACER if tracer is None else tracer
        from .assignment import FlatAssignState

        #: fresh-port affinity bias (tau-aware only; see FlatAssignState):
        #: keeps each port's resources on few cores so the pending set's
        #: resource-sharing graph fragments — what gives delta-scheduling
        #: untouched components to splice
        self.locality = float(locality)
        self._assign = FlatAssignState(policy, self.rates, self.delta, self.N,
                                       seed=seed, locality=self.locality)
        n_res = self.K * self.N
        #: committed circuit horizons per (core, port) resource
        self.free_in = np.zeros(n_res)
        self.free_out = np.zeros(n_res)
        self.t_now = 0.0
        self._ticks = 0
        self._pend = {name: np.zeros(0, dtype=dt) for name, dt in _PEND_FIELDS}
        # -- delta-scheduling (touched-set) cache ---------------------------
        #: re-run the event loop only over the resource-sharing components a
        #: new arrival touches, splicing cached tentative times for the rest
        #: (bit-identical to the full tentative replay; see _touched_rows and
        #: cross_check_incremental's delta-vs-full gate)
        self.delta_schedule = bool(delta_schedule)
        #: cached tentative t_establish aligned row-for-row with ``_pend``;
        #: ``None`` = no valid cache (first tick, or a fault perturbed the
        #: pending set / horizons / delays out from under it)
        self._tent: np.ndarray | None = None
        #: per-row validity of ``_tent`` (same alignment): a fault
        #: invalidates only the rows whose components it actually perturbed
        #: (see ``_apply_fault``); invalid rows seed the next tick's touched
        #: set exactly like new arrivals. ``None`` iff ``_tent`` is None.
        self._tent_valid: np.ndarray | None = None
        #: escape hatch for the fault-scoped invalidation: ``False`` drops
        #: the whole cache on any fault (the pre-PR-10 behavior) — the
        #: differential tests twin-drive both settings and assert
        #: bit-identical commits
        self._fault_scoped_tent = True
        #: incremental component index maintained across ticks/faults; None
        #: when delta-scheduling is off or reserving commits everything
        #: immediately (no tentative rows to splice)
        self._cindex: ComponentIndex | None = (
            ComponentIndex(n_res)
            if delta_schedule and scheduling != "reserving" else None)
        #: delta-scheduling effectiveness counters (rows spliced from the
        #: cache vs rows re-run through the event loop, cumulative)
        self.tent_reused = 0
        self.tent_recomputed = 0
        #: tentative rows invalidated by fault-scoped cache surgery
        #: (cumulative; rows a full drop would also have re-derived)
        self.tent_invalidated = 0
        #: resource-component telemetry (cumulative over ticks): how many
        #: components the pending sets decomposed into, and how many of
        #: them ticks actually re-scheduled — the ROADMAP's
        #: delta-scheduling-leverage diagnostic
        self.components_total = 0
        self.components_touched = 0
        #: per-tick component-size histograms (cumulative over ticks):
        #: {rows-per-component: occurrences} for every component seen, and
        #: for the components whose cached rows were spliced untouched —
        #: the *where does the splice fail* diagnostic bench_overload emits
        self.component_size_hist: dict[int, int] = {}
        self.component_reused_hist: dict[int, int] = {}
        # per-gid registry (appended at admission)
        self._cid: list[int] = []
        self._weight: list[float] = []
        self._release: list[float] = []
        self._nflows: list[int] = []
        self._ndone: list[int] = []
        self._cct: list[float] = []
        # -- fault model (core.fault) ---------------------------------------
        #: scripted fault schedule; ``step`` pops events due at each tick
        self.faults = faults
        #: retain committed circuits so faults can classify them; on by
        #: default whenever an injector is present (FabricManager always
        #: turns it on so report_fault works). With zero fault events the
        #: retention changes no computed value — the zero-event injector is
        #: bit-identical to a plain FabricState (fuzzed in
        #: tests/test_fault_differential.py).
        if track_commits is None:
            track_commits = faults is not None
        self.track_commits = bool(track_commits)
        self._commit = (
            {name: np.zeros(0, dtype=dt) for name, dt in _COMMIT_FIELDS}
            if self.track_commits else None)
        # -- committed-circuit retention GC ---------------------------------
        #: how far back a late-discovered fault may be timestamped; commits
        #: completing at or before ``t_now - fault_lookback`` can never be
        #: classified by an admissible event and are dropped (watermark GC)
        if not fault_lookback >= 0:
            raise ValueError("fault_lookback must be >= 0 (np.inf = retain "
                             "every commit forever)")
        self.fault_lookback = float(fault_lookback)
        self._gc_floor = -np.inf  # commits with t_comp <= floor are gone
        self.commits_gced = 0     # exact count of GCed commit rows
        #: per-gid max completion among GCed commits: keeps the running-CCT
        #: rollback exact when a fault unfinalizes a coflow whose earlier
        #: circuits were already collected
        self._gc_cct: list[float] = []
        self.core_up = np.ones(self.K, dtype=bool)
        #: per-core reconfiguration delay (DeltaDrift moves entries)
        self.delta_k = np.full(self.K, self.delta)
        self._drifted = False
        #: port-flap blackout floors per (core, port) resource
        self._flap_in = np.zeros(n_res)
        self._flap_out = np.zeros(n_res)
        self.fault_log: list = []  # FaultApplication records, in order

    # -- registry views ----------------------------------------------------
    @property
    def n_coflows(self) -> int:
        """Coflows admitted so far (finalized or not)."""
        return len(self._cid)

    @property
    def commit_floor(self) -> float:
        """Latest committed decision boundary: releases at or before it can
        no longer be admitted bit-exactly (-inf before the first tick)."""
        return self.t_now if self._ticks else -np.inf

    @property
    def n_pending_flows(self) -> int:
        return int(self._pend["gid"].size)

    @property
    def delta_drifted(self) -> bool:
        """True while any core's reconfiguration delay is off-nominal."""
        return bool(self._drifted)

    @property
    def n_commits_retained(self) -> int:
        """Committed circuits currently retained for fault classification
        (0 without commit tracking)."""
        c = self._commit
        return int(c["gid"].size) if c is not None else 0

    def ccts(self) -> Annotated[F8, "G"]:
        """Running per-coflow CCTs indexed by gid (final once finalized)."""
        return np.asarray(self._cct, dtype=np.float64)

    def weights(self) -> Annotated[F8, "G"]:
        return np.asarray(self._weight, dtype=np.float64)

    # -- fault model --------------------------------------------------------
    def aborted_keys(self) -> set:
        """Program-segment keys of every circuit aborted by a fault so far
        (see ``fault.AbortedCircuit.key``) — the stream-wide program must
        exclude these segments (``service.FabricManager.program`` does)."""
        return {a.key for app in self.fault_log for a in app.aborted}

    def _rebuild_horizons(self) -> None:
        """Recompute the committed-circuit horizons from the retained
        commits, then fold in flap floors and failed-core ``+inf``.

        ``max`` is an exact selection, so the rebuilt values equal what the
        incremental ``np.maximum.at`` updates accumulated — minus the
        contributions of circuits a fault just aborted.
        """
        n_res = self.K * self.N
        free_in = np.zeros(n_res)
        free_out = np.zeros(n_res)
        c = self._commit
        if c is not None and c["gid"].size:
            np.maximum.at(free_in, c["core"] * self.N + c["fi"], c["t_comp"])
            np.maximum.at(free_out, c["core"] * self.N + c["fj"], c["t_comp"])
        np.maximum(free_in, self._flap_in, out=free_in)
        np.maximum(free_out, self._flap_out, out=free_out)
        down = np.repeat(~self.core_up, self.N)
        free_in[down] = np.inf
        free_out[down] = np.inf
        self.free_in = free_in
        self.free_out = free_out

    @effects("commit-mutate", "watermark")
    def _gc_commits(self, t_now: float) -> None:
        """Watermark GC over the retained commits (satellite of the fault
        model): a fault discovered late may be timestamped no earlier than
        ``t_now - fault_lookback``, and classification only aborts circuits
        with ``t_comp > t_fault``, so commits completing at or before the
        watermark can never be aborted again — drop them.

        Dropping is also invisible to scheduling: a GCed ``t_comp`` is
        ``<= gc_floor <= t_now``, and every future event-loop seed /
        reservation start is ``>= t_now`` (``max`` semantics make values at
        or below ``t0`` equivalent), so horizon rebuilds after later faults
        compute the same floats with or without the dropped rows. The only
        value they still feed — a re-opened coflow's running CCT — is kept
        exact through the per-gid ``_gc_cct`` max.
        """
        if not np.isfinite(self.fault_lookback):
            return
        if np.isfinite(t_now):
            # finalize()'s t=inf tick is end-of-stream bookkeeping, not the
            # passage of time: it does not advance the watermark
            wm = t_now - self.fault_lookback
            if wm > self._gc_floor:
                self._gc_floor = wm
        c = self._commit
        if c is None or not c["gid"].size or self._gc_floor == -np.inf:  # reprolint: disable=float-eq -- -inf is an exact sentinel (never produced by arithmetic)
            return
        drop = c["t_comp"] <= self._gc_floor
        n_drop = int(drop.sum())
        if not n_drop:
            return
        for g, v in zip(c["gid"][drop].tolist(), c["t_comp"][drop].tolist()):
            if v > self._gc_cct[g]:
                self._gc_cct[g] = v
        self._commit = {name: c[name][~drop] for name, _dt in _COMMIT_FIELDS}
        self.commits_gced += n_drop

    def _requeue(self, moved: dict, t_f: float, bump_release: np.ndarray
                 ) -> None:
        """Reassign flows over the up cores and append them to the pending
        set. ``moved`` holds ``_PEND_FIELDS`` arrays; rows with
        ``bump_release`` True (aborted in-flight circuits) can restart no
        earlier than the fault time ``t_f``."""
        rel = moved["rel"].copy()
        rel[bump_release] = np.maximum(rel[bump_release], t_f)
        order = np.lexsort((moved["intra"], moved["gid"]))
        fi, fj = moved["fi"][order], moved["fj"][order]
        sizes = moved["size"][order]
        core = self._assign.assign(fi, fj, sizes, up=self.core_up)
        if self._cindex is not None:
            self._cindex.add(core * self.N + fi, core * self.N + fj)
        add = {
            "gid": moved["gid"][order], "cid": moved["cid"][order],
            "fi": fi, "fj": fj, "core": core, "size": sizes,
            "srv": sizes / self.rates[core], "rel": rel[order],
            "score": moved["score"][order], "intra": moved["intra"][order],
        }
        self._pend = {
            name: np.concatenate([self._pend[name], add[name]])
            for name, _dt in _PEND_FIELDS
        }

    @effects("commit-mutate", "fingerprint-mutate", "watermark",
             "rng-consume", "trace-emit")
    def apply_fault(self, event: "FaultEvent") -> "FaultApplication":
        """Apply one topology-churn event (see ``core.fault``) right now.

        Committed circuits interrupted by the event are aborted (their
        demand re-queued, reassigned over the surviving cores, their ports'
        horizons rolled back), tentative flows stranded on a failed core are
        reassigned, and retracted final CCTs are reported. Returns the
        ``FaultApplication`` record; ``step`` calls this for every injector
        event due at a tick, ``service.FabricManager.report_fault`` for
        events discovered between ticks. The recovery is recorded as one
        ``fault/recover`` span carrying the abort/requeue counts.
        """
        with self._tracer.span("fault/recover") as sp:
            inv0 = self.tent_invalidated
            app = self._apply_fault(event)
            if sp.live:
                sp.set(event=type(app.event).__name__,
                       aborted=app.n_aborted, requeued=app.requeued,
                       reassigned=app.reassigned_pending,
                       unfinalized=len(app.unfinalized),
                       invalidated=self.tent_invalidated - inv0)
            return app

    @effects("commit-mutate", "fingerprint-mutate", "watermark",
             "rng-consume")
    def _apply_fault(self, event: "FaultEvent") -> "FaultApplication":
        from .fault import (
            FAULT_EVENTS,
            AbortedCircuit,
            CoreDown,
            CoreUp,
            DeltaDrift,
            FaultApplication,
            PortFlap,
        )

        if not isinstance(event, FAULT_EVENTS):
            raise TypeError(
                f"unknown fault event {event!r}; one of "
                f"{[cls.__name__ for cls in FAULT_EVENTS]}")
        t_f = float(event.t)
        k = int(event.core)
        if not 0 <= k < self.K:
            raise ValueError(f"core {k} out of range for K={self.K}")
        # Scoped tentative-cache invalidation (DESIGN.md §Delta-scheduling):
        # each event type stales only the rows whose next-tick estimates can
        # actually change — components never span cores, so the blast radius
        # of a fault on core k is expressible as a row mask or a component
        # set. `_fault_scoped_tent=False` restores the PR-6 full-drop path
        # (the twin-drive differential gate pins both bit-identical).
        if not self._fault_scoped_tent:
            if self._tent is not None and self.delta_schedule:
                self.tent_invalidated += int(self._tent.size)
            self._tent = None
            self._tent_valid = None

        def _stale(mask: np.ndarray) -> None:
            # mark cached rows stale; they seed the next tick's dirty set
            if (self._tent is None or self._tent_valid is None
                    or not self.delta_schedule):
                return
            flip = mask & self._tent_valid
            n = int(flip.sum())
            if n:
                self._tent_valid[flip] = False
                self.tent_invalidated += n

        def _done(aborted: Sequence = (), requeued: int = 0,
                  reassigned: int = 0,
                  unfinalized: Sequence = ()) -> "FaultApplication":
            app = FaultApplication(
                event=event, aborted=tuple(aborted), requeued=int(requeued),
                reassigned_pending=int(reassigned),
                unfinalized=tuple(unfinalized))
            self.fault_log.append(app)
            return app

        if isinstance(event, DeltaDrift):
            self.delta_k[k] = float(event.delta)
            self._drifted = bool(np.any(self.delta_k != self.delta))
            self._assign.set_delta(k, float(event.delta))
            # the reconfiguration delay is priced per core: only core-k
            # rows (= the union of core-k components) see new estimates
            _stale(self._pend["core"] == k)
            return _done()

        if isinstance(event, CoreUp):
            if self.core_up[k]:
                raise ValueError(f"core {k} is already up")
            self.core_up[k] = True
            # The dead core delivered nothing while down and its interrupted
            # circuits were re-queued elsewhere, so its true future load is
            # zero: reset the greedy assignment state's view of it, or the
            # stale historical load would under-use the recovered core
            # indefinitely (it converges back toward the healthy mix —
            # asserted in tests/test_fault_residue.py).
            self._assign.reset_core(k)
            self._rebuild_horizons()
            # no cache invalidation: the commit set is unchanged (so the
            # rebuilt horizons hold the same floats) and a recovered core
            # has no pending rows — every cached estimate stands
            return _done()

        # CoreDown / PortFlap must classify the committed circuits.
        if self._commit is None:
            raise RuntimeError(
                "this FabricState was built without commit tracking and "
                "cannot classify committed circuits on a "
                f"{type(event).__name__}; rebuild it with "
                "track_commits=True or a FaultInjector")
        if t_f < self._gc_floor:
            raise ValueError(
                f"fault at t={t_f} predates the committed-circuit retention "
                f"watermark t={self._gc_floor} (fault_lookback="
                f"{self.fault_lookback}): the commits it would classify have "
                f"been garbage-collected; widen fault_lookback or report "
                f"faults sooner")
        c = self._commit
        strand = np.zeros(self._pend["gid"].size, dtype=bool)
        if isinstance(event, CoreDown):
            if not self.core_up[k]:
                raise ValueError(f"core {k} is already down")
            if self.core_up.sum() == 1:
                raise RuntimeError(
                    f"cannot fail core {k}: it is the last core up "
                    f"(fabric lost)")
            self.core_up[k] = False
            # in-flight (or not-yet-established but already programmed)
            # circuits on the core deliver nothing; completed ones are kept
            abort = (c["core"] == k) & (c["t_comp"] > t_f)
            strand = self._pend["core"] == k
        else:  # PortFlap
            p = int(event.port)
            if not 0 <= p < self.N:
                raise ValueError(f"port {p} out of range for N={self.N}")
            t_end = float(event.t_end)
            r = k * self.N + p
            self._flap_in[r] = max(self._flap_in[r], t_end)
            self._flap_out[r] = max(self._flap_out[r], t_end)
            touches = (c["core"] == k) & ((c["fi"] == p) | (c["fj"] == p))
            abort = touches & (c["t_est"] < t_end) & (c["t_comp"] > t_f)

        aborted_rows = {name: c[name][abort] for name, _dt in _COMMIT_FIELDS}
        self._commit = {name: c[name][~abort] for name, _dt in _COMMIT_FIELDS}
        # PortFlap: the flap floor rose on resource r and the aborted
        # circuits' horizon rollback moves their endpoint resources — stale
        # every cached row whose component reaches one of those nodes.
        # (CoreDown needs no mask: components never span cores, so the
        # blast radius is exactly the strand rows removed below, and the
        # survivors' horizons keep their untouched-core floats.)
        if (isinstance(event, PortFlap) and self._cindex is not None
                and self._tent is not None and self._pend["gid"].size):
            nr = self._cindex.n_res
            ab_core = aborted_rows["core"]
            nodes = np.unique(np.concatenate([
                np.asarray([r, r + nr], dtype=np.int64),
                (ab_core * self.N + aborted_rows["fi"]).astype(np.int64),
                (ab_core * self.N + aborted_rows["fj"]).astype(np.int64)
                + nr,
            ]))
            row_lab = self._cindex.labels(
                (self._pend["core"] * self.N
                 + self._pend["fi"]).astype(np.int64))
            _stale(np.isin(row_lab, self._cindex.labels(nodes)))
        # stranded rows leave the pending set (and so the index); their
        # re-queued successors re-enter through _requeue's add below
        if self._cindex is not None and strand.any():
            pr = self._pend["core"][strand] * self.N
            self._cindex.remove(pr + self._pend["fi"][strand],
                                pr + self._pend["fj"][strand])
        records = tuple(
            AbortedCircuit(
                gid=int(aborted_rows["gid"][x]),
                cid=int(aborted_rows["cid"][x]),
                i=int(aborted_rows["fi"][x]), j=int(aborted_rows["fj"][x]),
                core=int(aborted_rows["core"][x]),
                size=float(aborted_rows["size"][x]),
                t_establish=float(aborted_rows["t_est"][x]),
                t_abort=t_f)
            for x in range(aborted_rows["gid"].size))
        # registry rollback: a finalized coflow losing a circuit is
        # un-finalized; its running CCT is recomputed from what survives
        unfinalized = []
        gids_ab, counts_ab = np.unique(aborted_rows["gid"],
                                       return_counts=True)
        for g, n in zip(gids_ab.tolist(), counts_ab.tolist()):
            if self._ndone[g] == self._nflows[g]:
                unfinalized.append(g)
            self._ndone[g] -= n
            # recompute the running CCT from what survives; GCed circuits of
            # this coflow (inside the watermark they completed, so they can
            # no longer be aborted) contribute through the exact per-gid max
            rem = self._commit["t_comp"][self._commit["gid"] == g]
            base = self._gc_cct[g]
            self._cct[g] = float(max(float(rem.max()), base)) if rem.size \
                else base

        moved = {
            name: np.concatenate(
                [aborted_rows[name], self._pend[name][strand]])
            for name, _dt in _PEND_FIELDS
        }
        self._pend = {name: self._pend[name][~strand]
                      for name, _dt in _PEND_FIELDS}
        if moved["gid"].size:
            bump = np.zeros(moved["gid"].size, dtype=bool)
            bump[:aborted_rows["gid"].size] = True
            self._requeue(moved, t_f, bump)
        # realign the tentative cache with the post-fault pending set:
        # drop strand entries, append invalid placeholders for re-queued
        # rows (placeholders are never spliced — an invalid row always
        # seeds the dirty set, so its component re-runs the event loop)
        if self._tent is not None and self._tent_valid is not None:
            if self._tent.size != strand.size:
                self._tent = None
                self._tent_valid = None
            else:
                if strand.any():
                    if self.delta_schedule:
                        self.tent_invalidated += int(
                            self._tent_valid[strand].sum())
                    self._tent = self._tent[~strand]
                    self._tent_valid = self._tent_valid[~strand]
                n_add = int(self._pend["gid"].size) - self._tent.size
                if n_add > 0:
                    self._tent = np.concatenate(
                        [self._tent, np.zeros(n_add)])
                    self._tent_valid = np.concatenate(
                        [self._tent_valid, np.zeros(n_add, dtype=bool)])
        self._rebuild_horizons()
        return _done(aborted=records, requeued=aborted_rows["gid"].size,
                     reassigned=int(strand.sum()), unfinalized=unfinalized)

    # -- admission + scheduling -------------------------------------------
    def _admit(self, coflows: Sequence[Coflow],
               releases: np.ndarray) -> dict:
        """Register a batch and return its pending-flow arrays in
        within-batch arrival order (release, then WSPT score desc, then
        submission order) — the global arrival order's restriction to the
        batch, since every earlier admission has a strictly earlier
        release bucket."""
        from .ordering import priority_scores

        B = len(coflows)
        gid0 = self.n_coflows
        for c in coflows:
            if c.n_ports != self.N:
                raise ValueError(
                    f"coflow {c.cid} has N={c.n_ports}, fabric has N={self.N}")
        # the batch's WSPT scores, through the one shared definition (scores
        # are per-coflow, so the batch sub-instance computes the same floats
        # the full-stream replay would). Scores price the *surviving* fabric
        # (R over up cores): with a core down from t=0 this is exactly the
        # (K-1)-core instance's score, which the fault differential relies
        # on; with every core up the masked view holds the same floats.
        scores = priority_scores(Instance(
            coflows=tuple(coflows), rates=self.rates[self.core_up],
            delta=self.delta))
        for c, r in zip(coflows, releases):
            self._cid.append(int(c.cid))
            self._weight.append(float(c.weight))
            self._release.append(float(r))
            self._nflows.append(c.num_flows)
            self._ndone.append(0)
            self._cct.append(0.0)
            self._gc_cct.append(0.0)
        order = np.lexsort((np.arange(B), -scores, releases))
        batch = tuple(coflows[int(b)] for b in order)
        inst_b = Instance(coflows=batch, rates=self.rates, delta=self.delta)
        pos, cid, fi, fj, sizes = extract_flows(inst_b, np.arange(B))
        gid = gid0 + order[pos]
        core = self._assign.assign(
            fi, fj, sizes,
            up=None if self.core_up.all() else self.core_up)
        srv = sizes / self.rates[core]
        counts = np.bincount(pos, minlength=B)
        starts = np.cumsum(counts) - counts
        intra = np.arange(pos.size) - starts[pos]
        return {
            "gid": gid, "cid": cid,
            "fi": fi, "fj": fj, "core": core, "size": sizes, "srv": srv,
            "rel": releases[order][pos], "score": scores[order][pos],
            "intra": intra,
        }

    @effects("commit-mutate", "fingerprint-mutate", "watermark",
             "rng-consume", "trace-emit")
    def step(self, coflows: Sequence[Coflow],
             releases: Annotated[F8, "B"], t_now: float) -> TickCommit:
        """One service tick: admit ``coflows`` (released in
        ``(previous tick, t_now]``), schedule all pending flows against the
        committed horizons, and commit every circuit establishing at or
        before ``t_now``."""
        t_now = float(t_now)
        releases = np.asarray(releases, dtype=np.float64)
        if len(coflows) != releases.size:
            raise ValueError(
                f"got {len(coflows)} coflows but {releases.size} releases")
        if t_now < self.t_now:
            raise ValueError(
                f"tick times must be non-decreasing: {t_now} < {self.t_now}")
        if releases.size:
            lo = releases.min()
            if lo < 0:
                raise ValueError("release times must be >= 0")
            if self._ticks and lo <= self.t_now:
                raise ValueError(
                    f"late arrival: release {lo} is not after the previous "
                    f"tick at t={self.t_now} — its circuits may already be "
                    f"committed (clamp the release or tick more often)")
            if releases.max() > t_now:
                raise ValueError(
                    f"cannot admit a coflow released at {releases.max()} at "
                    f"tick t={t_now}; queue it until its release")
        # Topology churn due at this tick is applied after argument
        # validation (so a rejected batch consumes no injector events) and
        # BEFORE admission: the control plane learns of a fault when it
        # wakes, so this tick's arrivals are assigned over the surviving
        # cores and the tentative schedule below is re-derived for them.
        fault_apps = ()
        if self.faults is not None:
            fault_apps = tuple(
                self.apply_fault(ev) for ev in self.faults.pop_due(t_now))
        t_prev = self.t_now
        n_old = self._pend["gid"].size
        if len(coflows):
            with self._tracer.span("tick/assign") as sp_as:
                batch = self._admit(coflows, releases)
                if sp_as.live:
                    sp_as.set(coflows=len(coflows),
                              flows=int(batch["gid"].size))
            pend = {
                name: np.concatenate([self._pend[name], batch[name]])
                for name, _dt in _PEND_FIELDS
            }
        else:
            pend = self._pend
        n_res = self.K * self.N
        rin = pend["core"] * self.N + pend["fi"]
        rout = pend["core"] * self.N + pend["fj"]
        # keep the incremental component index in lock-step with the
        # pending set: the arrival batch's resource pairs enter here
        if self._cindex is not None and rin.size > n_old:
            self._cindex.add(rin[n_old:], rout[n_old:])
        # per-flow reconfiguration delay; scalar fast path unless a
        # DeltaDrift moved some core off the nominal delta
        dl_f = None if not self._drifted else self.delta_k[pend["core"]]
        comp_total = comp_touched = 0
        if self.scheduling == "reserving":
            # Reservations commit immediately in arrival order and never
            # move, so the horizon arrays ARE the reservation state.
            with self._tracer.span("tick/event_loop") as sp_ev:
                t_est = _reserving_times(
                    rin, rout, pend["srv"],
                    self.delta if dl_f is None else dl_f, n_res,
                    release=pend["rel"], avail_in=self.free_in,
                    avail_out=self.free_out)
                if sp_ev.live:
                    sp_ev.set(rows=int(t_est.size), reserving=True)
            commit = np.ones(t_est.size, dtype=bool)
        else:
            # Delta-scheduling: tentative times are stable across ticks
            # unless new competitors share a resource component (the same
            # invariant behind commit finality — an event at or before the
            # previous tick can't be changed by later arrivals; an event
            # after it can only be changed by flows in the same component).
            # So the cached tentative times of untouched components are
            # spliced, and only the touched rows re-run the event loop.
            F = rin.size
            with self._tracer.span("tick/splice") as sp_spl:
                t_est = np.empty(F)
                # ONE component query per tick: the incremental index
                # answers both the touched-row mask and the telemetry the
                # splice used to derive from two from-scratch union-finds
                # (_touched_rows + _resource_components, the oracle pair
                # the differential suites still pin this against)
                roots = (self._cindex.labels(rin)
                         if self.delta_schedule and F else None)
                n_invalid = 0
                if (self.delta_schedule and self._tent is not None
                        and self._tent.size == n_old and n_old):
                    t_est[:n_old] = self._tent
                    # seeds = new arrivals + rows a fault invalidated; the
                    # dirty set is every row sharing a component with one
                    seed = np.zeros(F, dtype=bool)
                    seed[n_old:] = True
                    if self._tent_valid is not None:
                        invalid = ~self._tent_valid
                        n_invalid = int(invalid.sum())
                        seed[:n_old] |= invalid
                    touched = (np.unique(roots[seed]) if seed.any()
                               else roots[:0])
                    dirty = (np.isin(roots, touched) if touched.size
                             else np.zeros(F, dtype=bool))
                else:
                    dirty = np.ones(F, dtype=bool)
                    touched = None
                if roots is not None:
                    uniq, cnts = np.unique(roots, return_counts=True)
                    comp_total = int(uniq.size)
                    if touched is None:
                        comp_touched = comp_total
                        reused_cnts = cnts[:0]
                    elif touched.size:
                        comp_touched = int(touched.size)
                        reused_cnts = cnts[~np.isin(uniq, touched)]
                    else:
                        comp_touched = 0
                        reused_cnts = cnts
                    hist = self.component_size_hist
                    for s_, n_ in zip(*np.unique(cnts, return_counts=True)):
                        s_ = int(s_)
                        hist[s_] = hist.get(s_, 0) + int(n_)
                    if reused_cnts.size:
                        hist = self.component_reused_hist
                        for s_, n_ in zip(*np.unique(reused_cnts,
                                                     return_counts=True)):
                            s_ = int(s_)
                            hist[s_] = hist.get(s_, 0) + int(n_)
                sub = np.nonzero(dirty)[0]
                self.tent_reused += int(F - sub.size)
                self.tent_recomputed += int(sub.size)
                if sp_spl.live:
                    sp_spl.set(reused=int(F - sub.size),
                               recomputed=int(sub.size),
                               invalidated=n_invalid,
                               components_total=comp_total,
                               components_touched=comp_touched)
            if sub.size:
                # Priority order: WSPT score desc, admission index,
                # intra-coflow extraction order — the global arrival
                # pipeline's flow order restricted to the (touched) pending
                # set; a component's restriction equals the global order's
                # restriction because components share no resources.
                with self._tracer.span("tick/event_loop") as sp_ev:
                    counts = LoopCounts() if sp_ev.live else None
                    perm = np.lexsort((pend["intra"][sub], pend["gid"][sub],
                                       -pend["score"][sub]))
                    s = sub[perm]
                    te = _event_loop(
                        rin[s], rout[s], pend["srv"][s], pend["core"][s],
                        self.delta if dl_f is None else dl_f[s], n_res,
                        self.N, t0=t_prev,
                        guard=(self.scheduling == "priority-guard"),
                        release=pend["rel"][s],
                        free_in0=self.free_in, free_out0=self.free_out,
                        counts=counts)
                    t_est[s] = te
                    if counts is not None:
                        sp_ev.set(rows=int(sub.size), events=counts.events,
                                  candidates=counts.candidates)
            commit = t_est <= t_now
        if dl_f is None:
            tc = (t_est[commit] + self.delta) + pend["srv"][commit]
        else:
            tc = (t_est[commit] + dl_f[commit]) + pend["srv"][commit]
        if self.scheduling != "reserving":
            np.maximum.at(self.free_in, rin[commit], tc)
            np.maximum.at(self.free_out, rout[commit], tc)
        if self.track_commits:
            newc = {name: pend[name][commit] for name, _dt in _PEND_FIELDS}
            newc["t_est"] = t_est[commit]
            newc["t_comp"] = tc
            self._commit = {
                name: np.concatenate([self._commit[name], newc[name]])
                for name, _dt in _COMMIT_FIELDS}
            self._gc_commits(t_now)
        finalized = []
        for g, v in zip(pend["gid"][commit].tolist(), tc.tolist()):
            self._ndone[g] += 1
            if v > self._cct[g]:
                self._cct[g] = v
            if self._ndone[g] == self._nflows[g]:
                finalized.append((g, self._cid[g], self._cct[g],
                                  self._weight[g]))
        if len(coflows):
            # zero-flow coflows finalize at admission with CCT 0.0
            for g in range(self.n_coflows - len(coflows), self.n_coflows):
                if self._nflows[g] == 0:
                    finalized.append((g, self._cid[g], 0.0, self._weight[g]))
        out = TickCommit(
            t_now=t_now,
            gid=pend["gid"][commit], cid=pend["cid"][commit],
            fi=pend["fi"][commit], fj=pend["fj"][commit],
            core=pend["core"][commit], size=pend["size"][commit],
            t_establish=t_est[commit], t_complete=tc,
            finalized=tuple(finalized),
            n_pending=int((~commit).sum()),
            delta_f=None if dl_f is None else dl_f[commit],
            faults=fault_apps,
            unfinalized=tuple(
                g for app in fault_apps for g in app.unfinalized),
            components_total=comp_total,
            components_touched=comp_touched,
        )
        self.components_total += comp_total
        self.components_touched += comp_touched
        if self._cindex is not None and commit.any():
            self._cindex.remove(rin[commit], rout[commit])
        self._pend = {name: pend[name][~commit] for name, _dt in _PEND_FIELDS}
        if self.scheduling == "reserving":
            self._tent = None
            self._tent_valid = None
        else:
            self._tent = t_est[~commit]
            # every surviving row was either spliced from a valid cache
            # entry or just re-derived by the event loop: all valid
            self._tent_valid = np.ones(self._tent.size, dtype=bool)
        self.t_now = t_now
        self._ticks += 1
        return out

    def finalize(self) -> TickCommit:
        """End-of-stream tick: commit every still-pending circuit."""
        return self.step((), (), np.inf)


def _assert_commits_equal(a: TickCommit, b: TickCommit, t: float) -> None:
    """Bit-exact equality of two TickCommits (delta-vs-full replay gate)."""
    for field in ("gid", "cid", "fi", "fj", "core", "size",
                  "t_establish", "t_complete"):
        va, vb = getattr(a, field), getattr(b, field)
        if not np.array_equal(va, vb):
            raise AssertionError(
                f"delta-scheduling/full-replay divergence at tick t={t}: "
                f"{field} differs ({va!r} vs {vb!r})")
    if a.finalized != b.finalized or a.n_pending != b.n_pending:
        raise AssertionError(
            f"delta-scheduling/full-replay divergence at tick t={t}: "
            f"finalized/pending bookkeeping differs")


def cross_check_incremental(
    oinst: OnlineInstance,
    algorithm: str = "ours",
    *,
    seed: int = 0,
    scheduling: str = "work-conserving",
    n_ticks: int = 8,
    tick_times: Annotated[F8, "T"] | None = None,
    compare_delta: bool = True,
) -> list[TickCommit]:
    """Differential gate for the incremental path: FabricState vs full replay.

    Streams ``oinst``'s coflows through a ``FabricState`` tick by tick
    (``tick_times``, or ``n_ticks`` evenly spaced over the arrival span) and
    asserts that the union of committed circuits is BIT-IDENTICAL — same
    flow set, same core choices, same establishment times, same per-coflow
    CCTs — to one ``run_fast_online`` call over the whole stream. The replay
    instance lists coflows in admission order (the service's identity
    order), which only re-labels ``oinst`` when releases are untied.

    ``compare_delta`` additionally drives a second ``FabricState`` with
    delta-scheduling disabled (full tentative replay every tick) through the
    identical tick sequence and asserts every tick's commit — flow set, core
    choices, establishment AND completion times, finalizations, pending
    count — is bit-identical to the delta-scheduled state's: the touched-set
    splice must be indistinguishable from recomputing the whole backlog.
    Returns the per-tick commits.
    """
    inst = oinst.inst
    rel = np.asarray(oinst.releases, dtype=np.float64)
    if tick_times is None:
        hi = float(rel.max()) if rel.size else 0.0
        tick_times = (np.linspace(hi / n_ticks, hi, n_ticks)
                      if hi > 0 else np.zeros(1))
    ticks = [float(t) for t in tick_times]
    if rel.size and (not ticks or ticks[-1] < float(rel.max())):
        ticks.append(float(rel.max()))
    batches, prev = [], -np.inf
    for T in ticks:
        batches.append(np.nonzero((rel > prev) & (rel <= T))[0])
        prev = T
    perm = np.concatenate(batches)
    if perm.size != inst.M:
        raise AssertionError("tick partition lost coflows (non-monotone ticks?)")
    replay = OnlineInstance(
        inst=Instance(coflows=tuple(inst.coflows[int(m)] for m in perm),
                      rates=inst.rates, delta=inst.delta),
        releases=rel[perm])
    fast = run_fast_online(replay, algorithm, seed=seed, scheduling=scheduling)

    st = FabricState(rates=inst.rates, delta=inst.delta, N=inst.N,
                     algorithm=algorithm, scheduling=scheduling, seed=seed,
                     delta_schedule=True)
    st_full = (FabricState(rates=inst.rates, delta=inst.delta, N=inst.N,
                           algorithm=algorithm, scheduling=scheduling,
                           seed=seed, delta_schedule=False)
               if compare_delta else None)
    commits = []
    for T, ids in zip(ticks, batches):
        cofs = [inst.coflows[int(m)] for m in ids]
        commits.append(st.step(cofs, rel[ids], T))
        if st_full is not None:
            _assert_commits_equal(
                commits[-1], st_full.step(cofs, rel[ids], T), T)
    commits.append(st.finalize())
    if st_full is not None:
        _assert_commits_equal(commits[-1], st_full.finalize(), np.inf)
        if not np.array_equal(st.ccts(), st_full.ccts()):
            raise AssertionError(
                "delta-scheduling/full-replay CCT divergence")
    if st.n_pending_flows:
        raise AssertionError("finalize left pending flows")

    inc = {}
    for c in commits:
        for t in range(c.n_flows):
            key = (int(c.gid[t]), int(c.fi[t]), int(c.fj[t]))
            if key in inc:
                raise AssertionError(f"flow {key} committed twice")
            inc[key] = (int(c.core[t]), float(c.t_establish[t]))
    ref = {}
    for f in fast.flows:
        ref[(int(fast.pi[f.coflow]), f.i, f.j)] = (f.core, f.t_establish)
    if set(inc) != set(ref):
        raise AssertionError(
            f"incremental/replay flow sets differ ({algorithm}, {scheduling}): "
            f"{len(inc)} vs {len(ref)} flows")
    for key, (core, te) in inc.items():
        if ref[key] != (core, te):
            raise AssertionError(
                f"incremental/replay mismatch at {key}: core/t_establish "
                f"{(core, te)!r} vs {ref[key]!r}")
    if not np.array_equal(st.ccts(), fast.ccts):
        worst = int(np.argmax(st.ccts() != fast.ccts))
        raise AssertionError(
            f"incremental/replay CCT mismatch at gid {worst}: "
            f"{st.ccts()[worst]!r} vs {fast.ccts[worst]!r}")
    return commits


def _oracle_assignment(inst: Instance, pi: np.ndarray, policy: str,
                       seed: int) -> Assignment:
    if policy == "tau-aware":
        return assign_tau_aware(inst, pi)
    if policy == "rho-only":
        return assign_rho_only(inst, pi)
    return assign_random(inst, pi, seed=seed)


#: Maximum kernel/assign_ref choice-disagreement *rate* accepted by the
#: pallas gate — matches the fp32 precision contract in
#: ``kernels.coflow_assign``. A single tie-break divergence is always allowed
#: regardless of F (on a tiny instance one expected flip would otherwise blow
#: the rate); an algorithmic error lands near a 1 - 1/K disagreement rate,
#: far above this.
_PALLAS_DIVERGENCE_CEILING = 0.03


def kernel_divergence(
    inst: Instance,
    flows: tuple[np.ndarray, ...],
    choices: Annotated[I8, "F"],
) -> tuple[int, int]:
    """``(diverged, allowed)`` of the Pallas kernel's choices.

    ``diverged`` counts the flows (``coflow.extract_flows`` order) whose core
    differs from ``kernels.ref.assign_ref`` evaluated at the kernel's
    fp32-cast inputs; ``allowed`` is the precision-contract allowance
    ``max(1, ceil(0.03 * F))``.
    """
    from repro.kernels.ref import assign_ref

    _pos, _cid, fi, fj, sizes = flows
    ref_c, _ = assign_ref(fi, fj, sizes.astype(np.float32),
                          inst.rates.astype(np.float32),
                          float(np.float32(inst.delta)), inst.N)
    diverged = int((np.asarray(choices) != ref_c.astype(np.int64)).sum())
    allowed = max(1, int(np.ceil(_PALLAS_DIVERGENCE_CEILING * fi.size)))
    return diverged, allowed


def _gate_choices(
    inst: Instance,
    pi: np.ndarray,
    policy: str,
    seed: int,
    backend: str,
) -> tuple[tuple[np.ndarray, ...], np.ndarray, Assignment | None]:
    """Assignment-phase differential gate.

    Returns ``(flat flows, choices, oracle assignment)`` — the dataclass
    oracle ``Assignment`` is built (and returned for reuse in the legacy
    replay) on the numpy path, ``None`` on the pallas path.

    numpy backend: the flat ``assign_fast`` choices must be bit-identical to
    the dataclass oracle's — and, for the tau-aware policy, to the kernel's
    fp64 reference ``kernels.ref.assign_ref`` as well (three independent
    implementations in lock-step). pallas backend: the kernel's choices are
    gated against ``assign_ref`` evaluated at the kernel's fp32-cast inputs;
    per the kernel's precision contract (fp32 accumulation vs assign_ref's
    fp64) occasional tie-break divergences are expected, so the gate bounds
    the divergence count (``max(1, ceil(0.03 * F))``) rather than asserting
    bit-equality.
    """
    flows = extract_flows(inst, pi)
    if backend == "pallas" and policy == "tau-aware":
        choices = _pallas_choices(inst, flows)
        diverged, allowed = kernel_divergence(inst, flows, choices)
        if diverged > allowed:
            raise AssertionError(
                f"pallas kernel/assign_ref diverge on {diverged}/{choices.size} "
                f"choices — beyond the precision-contract allowance ({allowed})")
        return flows, choices, None
    oracle_a = _oracle_assignment(inst, pi, policy, seed)
    oracle_choices = np.array(
        [af.core for per in oracle_a.flows for af in per], dtype=np.int64)
    choices = assign_fast(inst, pi, policy, seed=seed, flows=flows)
    if not np.array_equal(choices, oracle_choices):
        bad = int(np.argmax(choices != oracle_choices))
        raise AssertionError(
            f"assign_fast/{policy} choice mismatch with the dataclass oracle "
            f"at flow {bad}: {choices[bad]} vs {oracle_choices[bad]}")
    if policy == "tau-aware":
        try:
            from repro.kernels.ref import assign_ref
        except ImportError:  # core stays usable without jax
            return flows, choices, oracle_a
        _pos, _cid, fi, fj, sizes = flows
        ref_c, _ = assign_ref(fi, fj, sizes, inst.rates, inst.delta, inst.N)
        if not np.array_equal(choices, ref_c.astype(np.int64)):
            bad = int(np.argmax(choices != ref_c))
            raise AssertionError(
                f"assign_fast/assign_ref choice mismatch at flow {bad}: "
                f"{choices[bad]} vs {ref_c[bad]}")
    return flows, choices, oracle_a


def cross_check(
    inst: Instance,
    algorithm: str = "ours",
    *,
    seed: int = 0,
    scheduling: str = "work-conserving",
    atol: float = 1e-6,
    fast: Schedule | None = None,
    backend: str = "numpy",
) -> Schedule:
    """Differential gate: engine vs legacy oracle vs independent validator.

    Runs the batched engine AND the legacy per-core scheduler, asserts
    bit-level agreement of the assignment-phase core choices (flat
    ``assign_fast`` vs the dataclass oracle vs ``kernels.ref.assign_ref``;
    see ``_gate_choices``), per-coflow CCT agreement (within ``atol``; in
    practice bit-exact) and per-flow establishment-time agreement, then
    passes the engine schedule through ``simulator.validate``. Returns the
    engine schedule. Pass ``fast`` to check an engine schedule already
    computed for the same arguments instead of recomputing it.

    The legacy replay runs ``scheduler._schedule_from_assignment`` (the same
    per-core machinery ``scheduler.run`` dispatches to) on the gate's oracle
    assignment — already asserted choice-by-choice equal to what ``run``
    would rebuild, so rebuilding it would only duplicate the slow oracle
    assignment phase. ``backend="pallas"``: choices are gated against
    ``assign_ref`` at the kernel's fp32 inputs, and the replay uses the
    *engine's own* assignment (the kernel's fp32 tie-breaks may legitimately
    differ from the fp64 oracle's, so the replay isolates the scheduling
    phase under the kernel's precision contract).
    """
    from functools import partial

    from .circuit_scheduler import (
        schedule_core_list,
        schedule_core_reserving,
        schedule_core_sunflow,
    )
    from .scheduler import _schedule_from_assignment
    from .simulator import validate

    if fast is None:
        fast = run_fast(inst, algorithm, seed=seed, scheduling=scheduling,
                        backend=backend)
    pi = order_coflows(inst)
    policy, sched_eff = _resolve_algorithm(algorithm, scheduling)
    flows, choices, oracle_a = _gate_choices(inst, pi, policy, seed, backend)
    percore = {
        "work-conserving": schedule_core_list,
        "priority-guard": partial(schedule_core_list, guard=True),
        "reserving": schedule_core_reserving,
        "sunflow": schedule_core_sunflow,
    }[sched_eff]
    if oracle_a is None:  # pallas path: replay the engine's own choices
        oracle_a = assignment_from_choices(inst, pi, flows, choices)
    legacy = _schedule_from_assignment(inst, pi, oracle_a, percore)
    if not np.allclose(fast.ccts, legacy.ccts, atol=atol, rtol=0.0):
        worst = int(np.argmax(np.abs(fast.ccts - legacy.ccts)))
        raise AssertionError(
            f"engine/oracle CCT mismatch ({algorithm}, {scheduling}): coflow "
            f"{worst}: engine={fast.ccts[worst]!r} oracle={legacy.ccts[worst]!r}")
    key = lambda f: (f.core, f.coflow, f.i, f.j, f.size)
    fast_t = {key(f): f.t_establish for f in fast.flows}
    legacy_t = {key(f): f.t_establish for f in legacy.flows}
    if set(fast_t) != set(legacy_t):
        raise AssertionError(
            f"engine/oracle flow sets differ ({algorithm}, {scheduling})")
    for kf, te in fast_t.items():
        if abs(te - legacy_t[kf]) > atol:
            raise AssertionError(
                f"engine/oracle t_establish mismatch at {kf}: "
                f"{te!r} vs {legacy_t[kf]!r}")
    validate(fast)
    return fast


def cross_check_online(
    oinst: OnlineInstance,
    algorithm: str = "ours",
    *,
    seed: int = 0,
    scheduling: str = "work-conserving",
    atol: float = 1e-6,
    fast: Schedule | None = None,
    backend: str = "numpy",
) -> Schedule:
    """Online differential gate: engine vs ``run_online`` oracle vs validator.

    Runs ``run_fast_online`` AND the legacy per-core online oracle, asserts
    bit-level agreement of the arrival-order assignment choices (flat vs the
    ``_assign_at_arrival`` dataclass oracle; see ``_gate_choices``),
    per-coflow CCT and per-flow establishment-time agreement (within
    ``atol``; in practice bit-exact), then passes the engine schedule through
    the independent release-respecting ``simulator.validate``. Returns the
    engine schedule. Pass ``fast`` to check an engine schedule already
    computed for the same arguments instead of recomputing it.

    The oracle runs through ``run_online(assignment=...)``: its scheduling
    machinery (WSPT ordering, release gating, per-core event loops) runs in
    full, fed the gate's oracle assignment — already asserted
    choice-by-choice equal to what ``_assign_at_arrival`` would rebuild.
    ``backend="pallas"``: the replayed assignment is the *engine's own*
    kernel choices, so the comparison isolates the scheduling phase under
    the kernel's fp32 precision contract.
    """
    from .online import online_orders, run_online
    from .simulator import validate

    if fast is None:
        fast = run_fast_online(oinst, algorithm, seed=seed,
                               scheduling=scheduling, backend=backend)
    inst = oinst.inst
    rel = np.asarray(oinst.releases, dtype=np.float64)
    arrival, _ = online_orders(inst, rel)
    policy, _sched_eff = _resolve_algorithm(algorithm, scheduling)
    flows, choices, oracle_a = _gate_choices(inst, arrival, policy, seed,
                                             backend)
    if oracle_a is None:  # pallas path: replay the engine's own choices
        oracle_a = assignment_from_choices(inst, arrival, flows, choices)
    oracle = run_online(oinst, algorithm, seed=seed, scheduling=scheduling,
                        assignment=oracle_a)
    if not np.allclose(fast.ccts, oracle.ccts, atol=atol, rtol=0.0):
        worst = int(np.argmax(np.abs(fast.ccts - oracle.ccts)))
        raise AssertionError(
            f"online engine/oracle CCT mismatch ({algorithm}, {scheduling}): "
            f"coflow {worst}: engine={fast.ccts[worst]!r} "
            f"oracle={oracle.ccts[worst]!r}")
    key = lambda f: (f.core, f.coflow, f.i, f.j, f.size)
    fast_t = {key(f): f.t_establish for f in fast.flows}
    oracle_t = {key(f): f.t_establish for f in oracle.flows}
    if set(fast_t) != set(oracle_t):
        raise AssertionError(
            f"online engine/oracle flow sets differ ({algorithm}, {scheduling})")
    for kf, te in fast_t.items():
        if abs(te - oracle_t[kf]) > atol:
            raise AssertionError(
                f"online engine/oracle t_establish mismatch at {kf}: "
                f"{te!r} vs {oracle_t[kf]!r}")
    validate(fast, releases=oinst.releases)
    return fast

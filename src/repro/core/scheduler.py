"""Top-level multi-core coflow scheduling pipelines (OURS + the 4 baselines).

This module is the *reference oracle*: a direct, per-core transcription of
Algorithm 1 kept deliberately simple. The production path is
``repro.core.engine`` (vectorized, all cores in one call) — it is validated
against this module by ``engine.cross_check`` and the differential suite in
tests/test_engine_differential.py, and ``repro.core.run_batch`` maps whole
parameter grids over it. Prefer ``engine.run_fast``/``run_batch`` for
anything performance-sensitive; prefer ``run`` here when a second,
independent implementation is the point.
"""
from __future__ import annotations

import dataclasses
from typing import Annotated, Callable

import numpy as np

from .arrays import F8, I8
from .assignment import Assignment, assign_random, assign_rho_only, assign_tau_aware
from .circuit_scheduler import (
    ScheduledFlow,
    schedule_core_list,
    schedule_core_sunflow,
)
from .coflow import Instance
from .ordering import order_coflows

__all__ = ["FlowColumns", "Schedule", "run", "ALGORITHMS",
           "SUNFLOW_ALGORITHMS", "weighted_cct", "tail_cct", "tail_quantile"]


@dataclasses.dataclass(frozen=True)
class FlowColumns:
    """A schedule's flows as flat arrays, one entry a flow, in the order of
    ``Schedule.flows``; each field is the ``ScheduledFlow`` field of the
    same name (``coflow`` .. ``core`` int64, the rest float64)."""

    coflow: Annotated[I8, "F"]
    cid: Annotated[I8, "F"]
    i: Annotated[I8, "F"]
    j: Annotated[I8, "F"]
    core: Annotated[I8, "F"]
    size: Annotated[F8, "F"]
    t_establish: Annotated[F8, "F"]
    t_start: Annotated[F8, "F"]
    t_complete: Annotated[F8, "F"]

    @property
    def n_flows(self) -> int:
        return int(self.core.size)

    @classmethod
    def from_flows(cls, flows: list[ScheduledFlow]) -> "FlowColumns":
        n = len(flows)
        return cls(*(
            np.fromiter((getattr(r, name) for r in flows),
                        dtype=np.int64 if name in _INT_FIELDS else np.float64,
                        count=n)
            for name in _FIELDS))

    def to_flows(self) -> list[ScheduledFlow]:
        return list(map(ScheduledFlow,
                        *(getattr(self, name).tolist() for name in _FIELDS)))


_FIELDS = tuple(f.name for f in dataclasses.fields(FlowColumns))
_INT_FIELDS = _FIELDS[:5]


class Schedule:
    """A complete feasible schedule plus derived metrics.

    ``assignment`` is ``None`` on the flat engine path (``engine.run_fast``
    skips per-flow object materialization); the legacy oracle path and
    ``engine.schedule_all_cores`` always carry the full ``Assignment``, which
    the theory certificates (``theory.check_lemma2/3``) require.

    The flows (all cores; core-major, priority order within a core) come
    either as ``ScheduledFlow`` records (``flows=``, the oracle paths) or as
    ``FlowColumns`` (``columns=``, the flat engine). ``columns()`` gives the
    arrays either way; ``flows`` gives the records, built from the columns
    on first access and kept.
    """

    def __init__(self, inst: Instance, pi: np.ndarray,
                 assignment: Assignment | None, *, ccts: np.ndarray,
                 flows: list[ScheduledFlow] | None = None,
                 columns: FlowColumns | None = None) -> None:
        if (flows is None) == (columns is None):
            raise ValueError("a Schedule takes exactly one of flows, columns")
        self.inst = inst
        self.pi = pi
        self.assignment = assignment
        self.ccts = ccts            # (M,) indexed by ORIGINAL coflow id order
        self._flows = flows
        self._columns = columns

    @property
    def flows(self) -> list[ScheduledFlow]:
        if self._flows is None:
            self._flows = self._columns.to_flows()
        return self._flows

    def columns(self) -> FlowColumns:
        """The flows as arrays: the engine's own, or read off the records."""
        if self._columns is not None:
            return self._columns
        return FlowColumns.from_flows(self._flows)

    @property
    def n_flows(self) -> int:
        if self._columns is not None:
            return self._columns.n_flows
        return len(self._flows)

    @property
    def total_weighted_cct(self) -> float:
        return float((self.inst.weights * self.ccts).sum())

    @property
    def total_cct(self) -> float:
        return float(self.ccts.sum())

    def per_core_flows(self) -> dict[int, list[ScheduledFlow]]:
        out: dict[int, list[ScheduledFlow]] = {k: [] for k in range(self.inst.K)}
        for f in self.flows:
            out[f.core].append(f)
        return out


def _schedule_from_assignment(
    inst: Instance,
    pi: np.ndarray,
    assignment: Assignment,
    percore: Callable,
) -> Schedule:
    # Split assigned flows by core, keeping global priority order
    # (coflow position in pi, then the intra-coflow assignment order).
    per_core: list[list] = [[] for _ in range(inst.K)]
    for coflow_flows in assignment.flows:
        for af in coflow_flows:
            per_core[af.core].append(af)
    all_scheduled: list[ScheduledFlow] = []
    for k in range(inst.K):
        all_scheduled.extend(
            percore(per_core[k], k, float(inst.rates[k]), inst.delta, inst.N)
        )
    ccts = np.zeros(inst.M)
    for f in all_scheduled:
        orig = int(pi[f.coflow])
        ccts[orig] = max(ccts[orig], f.t_complete)
    return Schedule(inst=inst, pi=pi, assignment=assignment, flows=all_scheduled, ccts=ccts)


def run(
    inst: Instance,
    algorithm: str = "ours",
    *,
    seed: int = 0,
) -> Schedule:
    """Run one of the named algorithms end to end.

    ``ours``          : Alg. 1 (tau-aware assignment + work-conserving list scheduling)
    ``rho-assign``    : tau-blind assignment, same ordering/scheduling
    ``rand-assign``   : rate-proportional random assignment, same ordering/scheduling
    ``sunflow-core``  : Alg. 1 assignment, Sunflow per-core scheduling
    ``rand-sunflow``  : random assignment + Sunflow per-core scheduling

    The work-conserving scheduler reads Alg. 1 lines 23-31 literally: flows
    are scanned in pi order, and any flow whose two ports are idle starts.
    (The two other readings, the guard across coflows and strict in-order
    reservation, were measured and dropped; see EXPERIMENTS.md
    §Reproduction-notes on Lemma 3.)
    """
    pi = order_coflows(inst)
    if algorithm == "ours":
        a = assign_tau_aware(inst, pi)
        return _schedule_from_assignment(inst, pi, a, schedule_core_list)
    if algorithm == "rho-assign":
        a = assign_rho_only(inst, pi)
        return _schedule_from_assignment(inst, pi, a, schedule_core_list)
    if algorithm == "rand-assign":
        a = assign_random(inst, pi, seed=seed)
        return _schedule_from_assignment(inst, pi, a, schedule_core_list)
    if algorithm == "sunflow-core":
        a = assign_tau_aware(inst, pi)
        return _schedule_from_assignment(inst, pi, a, schedule_core_sunflow)
    if algorithm == "rand-sunflow":
        a = assign_random(inst, pi, seed=seed)
        return _schedule_from_assignment(inst, pi, a, schedule_core_sunflow)
    raise ValueError(f"unknown algorithm {algorithm!r}; one of {sorted(ALGORITHMS)}")


ALGORITHMS = ("ours", "rho-assign", "rand-assign", "sunflow-core", "rand-sunflow")

#: The SUNFLOW-CORE baselines: coflow-at-a-time per-core scheduling in
#: place of the work-conserving list scheduler.
SUNFLOW_ALGORITHMS = ("sunflow-core", "rand-sunflow")


def weighted_cct(s: Schedule) -> float:
    return s.total_weighted_cct


def tail_quantile(ccts: np.ndarray, q: float) -> float:
    """p-quantile of a per-coflow CCT array — the single definition of the
    paper's tail metric, shared by the full and metrics-only sweep paths.

    An empty instance (M == 0) has no CCT distribution; report 0.0 rather
    than letting ``np.quantile`` raise on an empty array.
    """
    if ccts.size == 0:
        return 0.0
    return float(np.quantile(ccts, q))


def tail_cct(s: Schedule, q: float) -> float:
    """p-quantile of per-coflow CCTs (e.g. q=0.95 / 0.99 for the paper's tails)."""
    return tail_quantile(s.ccts, q)

"""Circuit programs: the fabric manager's output artifact.

A :class:`CircuitProgram` is the compiled, per-core, time-ordered list of
circuit segments the fabric would physically program — one segment per
scheduled flow, holding the (ingress, egress) port matching from circuit
establishment through transmission completion (teardown). It is the boundary
object between the scheduling engine (``core.engine``) and the switch
hardware: everything downstream of here is establish/teardown events.

Programs are self-validating: :meth:`CircuitProgram.as_schedule` rebuilds a
``core.scheduler.Schedule`` (against the instance implied by the program's
own segments), so the independent referee ``core.simulator.validate`` checks
port exclusivity, not-all-stop timing, demand conservation, and CCT
consistency on every emitted program. Programs from successive service ticks
concatenate (:meth:`merge`) into the stream-wide program.
"""
from __future__ import annotations

import dataclasses
from typing import TYPE_CHECKING, Annotated, Iterator, Sequence

import numpy as np

from repro.core.arrays import F8, I8
from repro.core.coflow import Coflow, Instance
from repro.core.effects import effects
from repro.core.scheduler import FlowColumns, Schedule
from repro.obs.trace import current_tracer

if TYPE_CHECKING:
    from repro.core.engine import TickCommit

__all__ = ["CircuitEvent", "CircuitProgram", "compile_commit",
           "compile_schedule", "merge_programs"]

_EMPTY_I = np.zeros(0, dtype=np.int64)
_EMPTY_F = np.zeros(0, dtype=np.float64)


@dataclasses.dataclass(frozen=True)
class CircuitEvent:
    """One switch action: (un)program the (ingress -> egress) matching."""

    t: float
    core: int
    kind: str       # "establish" | "teardown"
    ingress: int
    egress: int
    cid: int        # coflow the circuit serves (telemetry)


@dataclasses.dataclass(frozen=True)
class CircuitProgram:
    """Per-core, time-ordered circuit segments over a K-core, N-port fabric.

    Segments are stored as flat arrays sorted by (core, establishment time,
    ingress port); a segment occupies its ingress and egress port on its
    core for [t_establish, t_complete) — establishment at ``t_establish``,
    transmission in [t_establish + delta, t_complete), teardown at
    ``t_complete``.
    """

    rates: Annotated[F8, "K"]
    delta: float
    N: int
    core: Annotated[I8, "S"]
    ingress: Annotated[I8, "S"]
    egress: Annotated[I8, "S"]
    cid: Annotated[I8, "S"]      # served coflow id
    size: Annotated[F8, "S"]     # bytes carried
    t_establish: Annotated[F8, "S"]
    t_complete: Annotated[F8, "S"]
    #: per-segment reconfiguration delay in force at establishment (fault
    #: model: ``core.fault.DeltaDrift`` gives cores individual delays);
    #: ``None`` means the uniform nominal ``delta``.
    delta_seg: Annotated[F8, "S"] | None = None

    @classmethod
    def empty(cls, rates: Annotated[F8, "K"], delta: float,
              N: int) -> "CircuitProgram":
        return cls(rates=np.asarray(rates, dtype=np.float64),
                   delta=float(delta), N=int(N), core=_EMPTY_I.copy(),
                   ingress=_EMPTY_I.copy(), egress=_EMPTY_I.copy(),
                   cid=_EMPTY_I.copy(), size=_EMPTY_F.copy(),
                   t_establish=_EMPTY_F.copy(), t_complete=_EMPTY_F.copy())

    @property
    def n_segments(self) -> int:
        return int(self.core.size)

    @property
    def K(self) -> int:
        return int(np.asarray(self.rates).shape[0])

    @property
    def makespan(self) -> float:
        return float(self.t_complete.max()) if self.n_segments else 0.0

    def events(self) -> Iterator[CircuitEvent]:
        """Time-ordered establish/teardown events (ties: teardown first,
        then by core — a port freed at t may be re-matched at t)."""
        S = self.n_segments
        t = np.concatenate([self.t_complete, self.t_establish])
        kind = np.concatenate([np.zeros(S, np.int64), np.ones(S, np.int64)])
        seg = np.concatenate([np.arange(S), np.arange(S)])
        for x in np.lexsort((self.core[seg], kind, t)):
            s = int(seg[x])
            yield CircuitEvent(
                t=float(t[x]), core=int(self.core[s]),
                kind="establish" if kind[x] else "teardown",
                ingress=int(self.ingress[s]), egress=int(self.egress[s]),
                cid=int(self.cid[s]))

    def per_core(self) -> dict[int, Annotated[I8, "*"]]:
        """Segment indices per core (already time-ordered within a core)."""
        return {k: np.nonzero(self.core == k)[0] for k in range(self.K)}

    def seg_delta(self) -> Annotated[F8, "S"]:
        """Per-segment reconfiguration delay, materialized."""
        if self.delta_seg is not None:
            return self.delta_seg
        return np.full(self.n_segments, self.delta)

    def merge(self, other: "CircuitProgram") -> "CircuitProgram":
        """Concatenate two programs (e.g. successive service ticks)."""
        return merge_programs([self, other], self.rates, self.delta, self.N)

    def as_schedule(self) -> Schedule:
        """Rebuild a ``Schedule`` for the instance the program itself serves.

        The reconstructed instance has one coflow per distinct ``cid`` (in
        first-establishment order) whose demand is the program's carried
        bytes — by construction demand conservation holds, so
        ``simulator.validate`` checks what a program can violate: port
        exclusivity, not-all-stop timing, and CCT consistency. For an
        end-of-stream program this equals the schedule of the true instance
        (asserted in tests/test_service.py).
        """
        uniq, inv = np.unique(self.cid, return_inverse=True)
        # positions in first-establishment order, to keep pi meaningful
        first = np.full(uniq.size, np.inf)
        if self.n_segments:
            np.minimum.at(first, inv, self.t_establish)
        rank = np.argsort(np.argsort(first, kind="stable"), kind="stable")
        pos = rank[inv]
        demands = np.zeros((uniq.size, self.N, self.N))
        np.add.at(demands, (pos, self.ingress, self.egress), self.size)
        order = np.argsort(rank, kind="stable")  # cid at each position
        coflows = tuple(
            Coflow(cid=int(uniq[c]), demand=demands[p])
            for p, c in enumerate(order))
        inst = Instance(coflows=coflows, rates=self.rates, delta=self.delta)
        ccts = np.zeros(uniq.size)
        np.maximum.at(ccts, pos, self.t_complete)
        columns = FlowColumns(
            coflow=pos, cid=self.cid, i=self.ingress, j=self.egress,
            core=self.core, size=self.size, t_establish=self.t_establish,
            t_start=self.t_establish + self.seg_delta(),
            t_complete=self.t_complete)
        return Schedule(inst=inst, pi=np.arange(uniq.size), assignment=None,
                        ccts=ccts, columns=columns)

    def drop(self, keys: set) -> "CircuitProgram":
        """Remove the segments whose ``(cid, ingress, egress, core,
        t_establish)`` identity is in ``keys`` — the aborted-circuit keys of
        the fault model (``engine.FabricState.aborted_keys``). The aborted
        establishments physically happened and are audited by the corrective
        teardown events; the *program of record* excludes them so that bytes
        are accounted exactly once and a recovered core's new circuits never
        collide with stale intervals."""
        if not keys:
            return self
        keep = np.array([
            (int(self.cid[s]), int(self.ingress[s]), int(self.egress[s]),
             int(self.core[s]), float(self.t_establish[s])) not in keys
            for s in range(self.n_segments)], dtype=bool)
        if keep.all():
            return self
        dseg = None if self.delta_seg is None else self.delta_seg[keep]
        return dataclasses.replace(
            self, core=self.core[keep], ingress=self.ingress[keep],
            egress=self.egress[keep], cid=self.cid[keep],
            size=self.size[keep], t_establish=self.t_establish[keep],
            t_complete=self.t_complete[keep], delta_seg=dseg)

    def validate(self) -> None:
        """Run the independent referee on this program."""
        from repro.core.simulator import validate

        validate(self.as_schedule(), flow_delta=self.delta_seg)


def merge_programs(programs: Sequence[CircuitProgram],
                   rates: Annotated[F8, "K"], delta: float,
                   N: int) -> CircuitProgram:
    """Concatenate any number of programs for one fabric (re-sorted)."""
    programs = list(programs)
    if not programs:
        return CircuitProgram.empty(rates, delta, N)
    rates = np.asarray(rates, dtype=np.float64)
    for p in programs:
        if (p.N != int(N) or p.delta != float(delta)  # reprolint: disable=float-eq -- fabric-identity check: programs merge only for bit-identical delta (cache keys hash the exact value)
                or not np.array_equal(p.rates, rates)):
            raise ValueError("cannot merge programs for different fabrics")
    cat = lambda attr: np.concatenate([getattr(p, attr) for p in programs])
    if any(p.delta_seg is not None for p in programs):
        dseg = np.concatenate([p.seg_delta() for p in programs])
    else:
        dseg = None
    return _sorted_program(rates, delta, N, cat("core"), cat("ingress"),
                           cat("egress"), cat("cid"), cat("size"),
                           cat("t_establish"), cat("t_complete"), dseg)


def _sorted_program(rates: np.ndarray, delta: float, N: int,
                    core: np.ndarray, ingress: np.ndarray,
                    egress: np.ndarray, cid: np.ndarray, size: np.ndarray,
                    t_est: np.ndarray, t_comp: np.ndarray,
                    delta_seg: np.ndarray | None = None) -> CircuitProgram:
    order = np.lexsort((ingress, t_est, core))
    return CircuitProgram(
        rates=np.asarray(rates, dtype=np.float64), delta=float(delta),
        N=int(N), core=core[order], ingress=ingress[order],
        egress=egress[order], cid=cid[order], size=size[order],
        t_establish=t_est[order], t_complete=t_comp[order],
        delta_seg=None if delta_seg is None else delta_seg[order])


def compile_commit(commit: "TickCommit", rates: Annotated[F8, "K"],
                   delta: float, N: int) -> CircuitProgram:
    """Compile one ``engine.TickCommit`` into its circuit program.

    The program's ``cid`` field carries the stream admission id
    (``TickCommit.gid``) — the service's coflow identity, unique across the
    stream even when submitted ``Coflow.cid`` values collide. A drifted
    tick's per-flow delays ride along as ``delta_seg``.
    """
    return _sorted_program(rates, delta, N, commit.core, commit.fi, commit.fj,
                           commit.gid, commit.size, commit.t_establish,
                           commit.t_complete, commit.delta_f)


@effects("trace-emit")
def compile_schedule(s: Schedule, *, index_labels: bool = False) -> CircuitProgram:
    """Compile a full ``Schedule`` (e.g. the one-shot cached path).

    ``index_labels=True`` labels segments with each coflow's ORIGINAL
    instance index instead of its ``cid`` — the canonical form the program
    cache stores, since indices are unique by construction and map to any
    later submission's cids with one array lookup.

    Traced as ``oneshot/emit`` (``segments``).
    """
    with current_tracer().span("oneshot/emit") as sp:
        c = s.columns()
        if sp.live:
            sp.set(segments=c.n_flows)
        if c.n_flows == 0:
            return CircuitProgram.empty(s.inst.rates, s.inst.delta, s.inst.N)
        labels = (np.asarray(s.pi, dtype=np.int64)[c.coflow] if index_labels
                  else c.cid)
        return _sorted_program(
            s.inst.rates, s.inst.delta, s.inst.N, c.core, c.i, c.j, labels,
            c.size, c.t_establish, c.t_complete)

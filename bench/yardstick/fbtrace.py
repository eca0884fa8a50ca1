"""The benchmark's own copy of the FB2010 traffic generator.

Copied from the program's ``core.trace`` (``synth_fb_trace`` and the
``restrict`` reading of ``sample_instance``, Section V-A of the paper) so that
the yardstick does not move when the program changes; ``bench/tests`` checks
that both still give identical instances. The copy returns plain arrays, not
the program's objects. It draws every coflow's sender shares in one call
(the same stream of numbers as the original's one call per reducer) and
builds the demand matrices of the picked coflows only.

The original benchmark (github.com/coflow/coflow-benchmark,
``FB2010-1Hr-150-0.txt``) records 526 coflows of a 150-rack MapReduce
cluster over one hour. It is not redistributable, so ``synth_fb_trace`` is a
calibrated surrogate of its published aggregate shape: about 60% narrow
coflows with MB-scale reducers, 30% medium, 10% wide ones with GB-scale
reducers that carry most of the bytes.
"""
from __future__ import annotations

import dataclasses

import numpy as np

N_RACKS = 150


@dataclasses.dataclass(frozen=True)
class TraceCoflow:
    cid: int
    arrival_ms: float
    mappers: tuple[int, ...]        # sender racks
    reducers: tuple[int, ...]       # receiver racks
    reducer_mb: tuple[float, ...]   # MB received per reducer


def synth_fb_trace(n_coflows: int = 526, seed: int = 2026) -> list[TraceCoflow]:
    """Calibrated surrogate of the FB2010 coflow benchmark (one hour)."""
    rng = np.random.default_rng(seed)
    arrivals = np.sort(rng.uniform(0, 3_600_000, n_coflows))
    out: list[TraceCoflow] = []
    for cid in range(n_coflows):
        u = rng.random()
        if u < 0.60:       # narrow and small
            n_map = int(rng.integers(1, 5))
            n_red = int(rng.integers(1, 5))
            scale_mb = rng.lognormal(mean=0.0, sigma=1.2)
        elif u < 0.90:     # medium
            n_map = int(rng.integers(5, 31))
            n_red = int(rng.integers(5, 31))
            scale_mb = rng.lognormal(mean=2.5, sigma=1.2)
        else:              # wide and heavy
            n_map = int(rng.integers(30, N_RACKS + 1))
            n_red = int(rng.integers(30, N_RACKS + 1))
            scale_mb = rng.lognormal(mean=5.5, sigma=1.0)
        mappers = tuple(int(x) for x in rng.choice(N_RACKS, size=n_map, replace=False))
        reducers = tuple(int(x) for x in rng.choice(N_RACKS, size=n_red, replace=False))
        red_mb = tuple(float(scale_mb * rng.lognormal(0.0, 0.75)) for _ in range(n_red))
        out.append(TraceCoflow(cid=cid, arrival_ms=float(arrivals[cid]),
                               mappers=mappers, reducers=reducers,
                               reducer_mb=red_mb))
    return out


@dataclasses.dataclass(frozen=True)
class Backlog:
    """One offline scheduling request: M coflows over an N-port fabric."""

    demands: np.ndarray   # (M, N, N) float64 MB, >= 0
    weights: np.ndarray   # (M,) float64, > 0

    @property
    def n_flows(self) -> int:
        return int(np.count_nonzero(self.demands))


def _draw(rng: np.random.Generator, coflows: list[TraceCoflow]) -> list:
    """Each coflow's (reducers, mappers) perturbation, +-20%, drawn in one
    call: the same numbers as one call per reducer, in coflow order."""
    shape = [(len(tc.reducers), len(tc.mappers)) for tc in coflows]
    flat = rng.uniform(0.8, 1.2, size=sum(r * m for r, m in shape))
    cuts = np.cumsum([r * m for r, m in shape])[:-1]
    return [u.reshape(rm) for u, rm in zip(np.split(flat, cuts), shape)]


def _shares(tc: TraceCoflow, u: np.ndarray) -> np.ndarray:
    """(reducers, mappers) MB: each reducer's bytes split over the senders
    in the proportions ``u``."""
    return u / u.sum(axis=1, keepdims=True) * np.asarray(tc.reducer_mb)[:, None]


def _demand(tc: TraceCoflow, shares: np.ndarray, port_of: np.ndarray,
            n_ports: int) -> np.ndarray:
    """N x N demand of one coflow: traffic between racks that are ports."""
    src = port_of[np.asarray(tc.mappers)]
    dst = port_of[np.asarray(tc.reducers)]
    rows, cols = np.nonzero((dst[:, None] >= 0) & (src[None, :] >= 0))
    d = np.zeros((n_ports, n_ports))
    # senders and receivers are distinct racks, so no (i, j) repeats
    d[src[cols], dst[rows]] = shares[rows, cols]
    return d


def _port_of(selected: np.ndarray, ports: np.ndarray) -> np.ndarray:
    port_of = np.full(N_RACKS, -1, np.int64)
    port_of[selected] = ports
    return port_of


def sample_backlog(trace: list[TraceCoflow], *, n_ports: int, n_coflows: int,
                   seed: int, weight_range: tuple[int, int] = (1, 10),
                   ) -> tuple[Backlog, np.ndarray, np.ndarray]:
    """The paper's sampling with ``machine_map="restrict"`` and
    ``uniform-int`` weights, exactly as ``core.trace.sample_instance``.

    ``n_ports`` racks are drawn as ports, only traffic between them is
    kept, and ``n_coflows`` coflows are drawn among those with any.
    Returns ``(backlog, selected racks, picked trace indices)``.
    """
    rng = np.random.default_rng(seed)
    selected = rng.choice(N_RACKS, size=n_ports, replace=False)
    port_of = _port_of(selected, np.arange(n_ports))
    draws = _draw(rng, trace)
    nonempty = [k for k, tc in enumerate(trace)
                if (port_of[np.asarray(tc.mappers)] >= 0).any()
                and (port_of[np.asarray(tc.reducers)] >= 0).any()]
    if not nonempty:
        raise ValueError("no coflow has traffic between the selected racks")
    pick = rng.choice(nonempty, size=n_coflows,
                      replace=len(nonempty) < n_coflows)
    lo, hi = weight_range
    weights = rng.integers(int(lo), int(hi) + 1, size=n_coflows).astype(np.float64)
    demands = np.stack([
        _demand(trace[int(k)], _shares(trace[int(k)], draws[int(k)]), port_of,
                n_ports) for k in pick])
    return (Backlog(demands=demands, weights=weights), selected,
            np.asarray(pick, np.int64))


def redraw_backlog(trace: list[TraceCoflow], selected: np.ndarray,
                   pick: np.ndarray, rng: np.random.Generator,
                   weight_range: tuple[int, int] = (1, 10)) -> Backlog:
    """The same coflows on the same racks, with fresh numbers.

    Keeps what sets a request's work (which coflows, on which racks, so
    its flow count and shape) and draws anew what the paper's procedure
    draws per instance: the sender shares, the weights, and which port
    each selected rack becomes.
    """
    n_ports = selected.size
    port_of = _port_of(selected, rng.permutation(n_ports))
    picked = [trace[int(k)] for k in pick]
    demands = np.stack([_demand(tc, _shares(tc, u), port_of, n_ports)
                        for tc, u in zip(picked, _draw(rng, picked))])
    lo, hi = weight_range
    weights = rng.integers(int(lo), int(hi) + 1, size=pick.size).astype(np.float64)
    return Backlog(demands=demands, weights=weights)

"""Facebook-trace-style workload generation (Section V-A).

The original benchmark (github.com/coflow/coflow-benchmark, ``FB2010-1Hr-150-0.txt``)
records 526 coflows from a 3000-machine / 150-rack MapReduce cluster, one line per
coflow::

    <coflow id> <arrival ms> <num mappers> <mapper racks...> <num reducers>
        <reducer:MB ...>

It is not redistributable here, so ``synth_fb_trace`` generates a calibrated
surrogate reproducing its published aggregate structure (heavy-tailed: most
coflows are narrow and small, while the widest ~10% carry the overwhelming
majority of bytes), and ``load_fb_trace`` parses the real file format when a
copy is available. ``sample_instance`` then applies the paper's procedure:
receiver-level bytes are split pseudo-uniformly across the coflow's senders
with a small random perturbation, machines are mapped onto N ports, and M
coflows are sampled.
"""
from __future__ import annotations

import dataclasses
from typing import Annotated, Any, Iterator

import numpy as np

from .arrays import F8
from .coflow import Coflow, Instance, OnlineInstance

__all__ = ["TraceCoflow", "synth_fb_trace", "load_fb_trace",
           "sample_instance", "sample_online_instance", "arrival_stream"]

N_RACKS = 150


@dataclasses.dataclass(frozen=True)
class TraceCoflow:
    cid: int
    arrival_ms: float
    mappers: tuple[int, ...]              # rack ids of senders
    reducers: tuple[int, ...]             # rack ids of receivers
    reducer_mb: tuple[float, ...]         # bytes received per reducer (MB)


def synth_fb_trace(n_coflows: int = 526, seed: int = 2026) -> list[TraceCoflow]:
    """Calibrated surrogate of the FB-2010 coflow benchmark.

    Mixture calibrated to the published shape of the benchmark: ~60% of
    coflows are narrow (<= 4x4) with MB-scale reducers, ~30% medium, ~10%
    wide (up to full 150 racks) with GB-scale reducers carrying most bytes.
    Arrival times are sorted uniforms over one hour — i.e. Poisson-process
    arrival times conditioned on the total count ``n_coflows`` (the order
    statistics of a homogeneous Poisson process on an interval are uniform),
    not an unconditional Poisson draw of the count itself. They are unused by
    the paper's simultaneous-release experiments but kept for trace fidelity.
    """
    rng = np.random.default_rng(seed)
    arrivals = np.sort(rng.uniform(0, 3_600_000, n_coflows))
    out: list[TraceCoflow] = []
    for cid in range(n_coflows):
        u = rng.random()
        if u < 0.60:       # narrow & small
            n_map = int(rng.integers(1, 5))
            n_red = int(rng.integers(1, 5))
            scale_mb = rng.lognormal(mean=0.0, sigma=1.2)        # ~1 MB median
        elif u < 0.90:     # medium
            n_map = int(rng.integers(5, 31))
            n_red = int(rng.integers(5, 31))
            scale_mb = rng.lognormal(mean=2.5, sigma=1.2)        # ~12 MB median
        else:              # wide & heavy
            n_map = int(rng.integers(30, N_RACKS + 1))
            n_red = int(rng.integers(30, N_RACKS + 1))
            scale_mb = rng.lognormal(mean=5.5, sigma=1.0)        # ~245 MB median
        mappers = tuple(int(x) for x in rng.choice(N_RACKS, size=n_map, replace=False))
        reducers = tuple(int(x) for x in rng.choice(N_RACKS, size=n_red, replace=False))
        red_mb = tuple(float(scale_mb * rng.lognormal(0.0, 0.75)) for _ in range(n_red))
        out.append(
            TraceCoflow(
                cid=cid,
                arrival_ms=float(arrivals[cid]),
                mappers=mappers,
                reducers=reducers,
                reducer_mb=red_mb,
            )
        )
    return out


def load_fb_trace(path: str) -> list[TraceCoflow]:
    """Parse the real ``FB2010-1Hr-150-0.txt`` benchmark format."""
    out: list[TraceCoflow] = []
    with open(path) as fh:
        lines = [ln.split() for ln in fh if ln.strip()]
    # First line may be a header: "<num machines> <num coflows>".
    if len(lines[0]) == 2:
        lines = lines[1:]
    for toks in lines:
        cid = int(toks[0])
        arrival = float(toks[1])
        n_map = int(toks[2])
        mappers = tuple(int(x) for x in toks[3 : 3 + n_map])
        n_red = int(toks[3 + n_map])
        red_toks = toks[4 + n_map : 4 + n_map + n_red]
        reducers, red_mb = [], []
        for rt in red_toks:
            r, mb = rt.split(":")
            reducers.append(int(r))
            red_mb.append(float(mb))
        out.append(
            TraceCoflow(
                cid=cid,
                arrival_ms=arrival,
                mappers=mappers,
                reducers=tuple(reducers),
                reducer_mb=tuple(red_mb),
            )
        )
    return out


def sample_instance(
    trace: list[TraceCoflow],
    *,
    N: int,
    M: int,
    rates: Annotated[F8, "K"],
    delta: float,
    seed: int = 0,
    weight_mode: str = "uniform-int",
    weight_params: tuple = (1, 10),
    machine_map: str = "restrict",
    return_pick: bool = False,
) -> "Instance | tuple[Instance, np.ndarray]":
    """Build an N-port, M-coflow instance per the paper's Section V-A.

    ``machine_map="restrict"`` (paper-faithful reading): N machines are
    randomly selected from the 150 racks; each becomes one ingress+egress
    port and only traffic between selected machines survives. M coflows are
    then sampled among those with nonzero restricted demand. This keeps the
    demand matrices sparse, so the reconfiguration term ``tau * delta`` is a
    first-order effect — the regime the paper's defaults (delta=8) target.

    ``machine_map="fold"``: alternative reading that maps all 150 racks onto
    the N ports via random grouping (permutation then mod N), keeping all
    bytes but densifying every wide coflow.

    Receiver-level bytes are split pseudo-uniformly over the coflow's
    senders with +-20% perturbation (paper Section V-A).

    ``return_pick=True`` additionally returns the picked trace indices
    (aligned with the instance's coflows) so callers can recover per-coflow
    trace metadata such as arrival stamps (see ``sample_online_instance``).
    """
    rng = np.random.default_rng(seed)

    if machine_map == "restrict":
        selected = rng.choice(N_RACKS, size=N, replace=False)
        port_of = {int(r): p for p, r in enumerate(selected)}
    elif machine_map == "fold":
        perm = rng.permutation(N_RACKS) % N
        port_of = {r: int(perm[r]) for r in range(N_RACKS)}
    else:
        raise ValueError(f"unknown machine_map {machine_map!r}")

    def build_demand(tc: TraceCoflow) -> np.ndarray:
        D = np.zeros((N, N))
        n_map = len(tc.mappers)
        for r_rack, mb in zip(tc.reducers, tc.reducer_mb):
            # Pseudo-uniform split across senders with small perturbation.
            shares = rng.uniform(0.8, 1.2, size=n_map)
            shares = shares / shares.sum() * mb
            for s_rack, share in zip(tc.mappers, shares):
                if s_rack in port_of and r_rack in port_of:
                    D[port_of[s_rack], port_of[r_rack]] += share
        return D

    demands = [build_demand(tc) for tc in trace]
    nonempty = [idx for idx, D in enumerate(demands) if D.any()]
    if not nonempty:
        raise ValueError("no coflow has traffic between the selected machines")
    pick = rng.choice(nonempty, size=M, replace=len(nonempty) < M)

    if weight_mode == "uniform-int":
        lo, hi = weight_params
        weights = rng.integers(int(lo), int(hi) + 1, size=M).astype(np.float64)
    elif weight_mode == "unit":
        weights = np.ones(M)
    elif weight_mode == "normal":
        mu, sigma = weight_params
        weights = np.maximum(rng.normal(mu, sigma, size=M), 1e-3)  # truncated
    else:
        raise ValueError(f"unknown weight_mode {weight_mode!r}")

    coflows = [
        Coflow(cid=m, demand=demands[int(t_idx)], weight=float(weights[m]))
        for m, t_idx in enumerate(pick)
    ]
    inst = Instance(coflows=tuple(coflows),
                    rates=np.asarray(rates, dtype=np.float64), delta=delta)
    if return_pick:
        return inst, np.asarray(pick, dtype=np.int64)
    return inst


def sample_online_instance(
    trace: list[TraceCoflow],
    *,
    N: int,
    M: int,
    rates: Annotated[F8, "K"],
    delta: float,
    span: float,
    seed: int = 0,
    **kw: Any,
) -> OnlineInstance:
    """Sample an instance WITH release times taken from the trace's arrival
    stamps — the streaming workload the fabric-manager service consumes.

    ``sample_instance`` discards the trace's ``arrival_ms`` column (the
    paper's experiments release everything at t=0); here each picked
    coflow's stamp is mapped affinely onto ``[0, span]`` (instance time
    units), keeping the trace's relative arrival structure — bursts stay
    bursts. ``span`` is typically a multiple of the offline makespan, as in
    ``benchmarks/online_arrivals.py``.
    """
    if span < 0:
        raise ValueError("span must be >= 0")
    inst, pick = sample_instance(trace, N=N, M=M, rates=rates, delta=delta,
                                 seed=seed, return_pick=True, **kw)
    if M == 0:
        return OnlineInstance(inst=inst, releases=np.zeros(0))
    arr = np.array([trace[int(t)].arrival_ms for t in pick])
    lo, hi = float(arr.min()), float(arr.max())
    rel = (np.zeros(M) if span == 0 or hi == lo  # reprolint: disable=float-eq -- degenerate-span guard: exact equality is the division-by-zero condition
           else (arr - lo) / (hi - lo) * span)
    return OnlineInstance(inst=inst, releases=rel)


def arrival_stream(oinst: OnlineInstance) -> Iterator[tuple[Coflow, float]]:
    """Yield ``(coflow, release)`` in arrival order — the event stream a
    fabric manager's admission queue sees (``service.FabricManager.submit``
    consumes exactly these pairs)."""
    rel = np.asarray(oinst.releases, dtype=np.float64)
    for m in np.argsort(rel, kind="stable"):
        yield oinst.inst.coflows[int(m)], float(rel[m])

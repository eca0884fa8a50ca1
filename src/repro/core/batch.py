"""Batched multi-instance sweep API over the vectorized scheduling engine.

``run_batch`` maps a whole parameter grid — instances x algorithms x
scheduling policies (x seeds) — to per-run ``Schedule`` metrics, optionally
fanning out across processes. Every run is gated by the differential-testing
harness: ``check="validate"`` (default) passes each schedule through the
independent feasibility validator, ``check="oracle"`` additionally replays
the legacy per-core scheduler and asserts exact agreement, so a sweep can
never silently drift from the reference algorithm.

Online grids get the SAME gating: an instance may be an ``OnlineInstance``
(or a per-instance ``releases`` array may be passed), in which case the grid
point runs ``engine.run_fast_online``, ``check="oracle"`` replays the
``online.run_online`` reference oracle, and the validator additionally
checks release respect.

The result is a flat, structured table (``ResultTable``) that the benchmark
scripts (``benchmarks/common.run_setting``, ``bench_core_scaling``,
``paper_*``) consume instead of hand-rolled dict aggregation.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Any, Iterable, Iterator, Sequence

import numpy as np

from repro.obs.clock import now

from .coflow import Instance, OnlineInstance
from .scheduler import ALGORITHMS, Schedule, tail_quantile

__all__ = ["SweepRow", "ResultTable", "run_batch", "row_from_ccts"]

_SUNFLOW_ALGS = ("sunflow-core", "rand-sunflow")


@dataclasses.dataclass(frozen=True)
class SweepRow:
    """Metrics of one (instance, algorithm, scheduling, seed) grid point."""

    instance: int          # index into the `instances` argument
    algorithm: str
    scheduling: str        # "sunflow" for the sunflow baselines
    seed: int
    weighted_cct: float
    total_cct: float
    p95: float
    p99: float
    makespan: float
    n_flows: int
    wall_s: float          # engine wall-clock for this run (excl. checks)

    def as_dict(self) -> dict:
        return dataclasses.asdict(self)


class ResultTable:
    """A list of ``SweepRow``s with pandas-free slicing helpers."""

    def __init__(self, rows: Sequence[SweepRow]) -> None:
        self.rows = list(rows)

    def __len__(self) -> int:
        return len(self.rows)

    def __iter__(self) -> Iterator[SweepRow]:
        return iter(self.rows)

    def filter(self, **where: Any) -> "ResultTable":
        """Rows matching all given column=value constraints."""
        out = [
            r for r in self.rows
            if all(getattr(r, k) == v for k, v in where.items())
        ]
        return ResultTable(out)

    def column(self, name: str, **where: Any) -> np.ndarray:
        """Column values of the rows matching ``where``.

        Raises ``ValueError`` when the filter matches no rows (a silent empty
        array used to flow into ``mean`` as RuntimeWarnings + NaN, hiding
        typos in filter values).
        """
        rows = self.filter(**where).rows
        if not rows:
            raise ValueError(
                f"no rows match filter {where!r} (table has {len(self.rows)} rows)")
        return np.array([getattr(r, name) for r in rows])

    def mean(self, name: str, **where: Any) -> float:
        return float(self.column(name, **where).mean())

    def to_dicts(self) -> list[dict]:
        return [r.as_dict() for r in self.rows]

    def __repr__(self) -> str:
        return f"ResultTable({len(self.rows)} rows)"


def _start_method() -> str:
    """Pick a multiprocessing start method for the sweep workers.

    fork is cheapest and works from any parent (including stdin/REPL "main"
    modules spawn can't re-import), but forking a process whose JAX runtime
    is live risks deadlocking on XLA's internal threads — so once jax is
    imported, prefer spawn whenever the main module is re-importable.
    Workers only ever run the numpy backend: ``run_batch`` keeps
    ``backend="pallas"`` grids in the calling process, which holds the chip.
    """
    import multiprocessing as mp
    import sys

    methods = mp.get_all_start_methods()
    if "fork" not in methods:
        return "spawn"
    if "jax" in sys.modules:
        main = sys.modules.get("__main__")
        main_file = getattr(main, "__file__", None)
        if getattr(main, "__spec__", None) is not None or (
                main_file and os.path.exists(main_file)):
            return "spawn"
    return "fork"


def _run_one(payload: tuple) -> SweepRow:
    """Worker body: one grid point -> SweepRow. Must stay picklable."""
    (idx, inst, rel, alg, sched, seed, check, backend, materialize) = payload
    from .engine import (
        cross_check,
        cross_check_online,
        run_fast,
        run_fast_metrics,
        run_fast_online,
    )

    if materialize == "metrics":
        t0 = now()
        ccts, n_flows = run_fast_metrics(inst, alg, seed=seed, scheduling=sched,
                                         backend=backend, releases=rel)
        wall = now() - t0
        return row_from_ccts(idx, alg, sched, seed, inst.weights, ccts,
                             n_flows, wall)
    t0 = now()
    if rel is None:
        s = run_fast(inst, alg, seed=seed, scheduling=sched, backend=backend)
    else:
        oinst = OnlineInstance(inst=inst, releases=rel)
        s = run_fast_online(oinst, alg, seed=seed, scheduling=sched,
                            backend=backend)
    wall = now() - t0
    if check == "oracle":
        if rel is None:
            cross_check(inst, alg, seed=seed, scheduling=sched, fast=s,
                        backend=backend)
        else:
            cross_check_online(oinst, alg, seed=seed, scheduling=sched, fast=s,
                               backend=backend)
    elif check == "validate":
        from .simulator import validate
        validate(s, releases=rel)
    return _row_from_schedule(idx, alg, sched, seed, s, wall)


def row_from_ccts(idx: int, alg: str, sched: str, seed: int,
                  weights: np.ndarray, ccts: np.ndarray, n_flows: int,
                  wall: float) -> SweepRow:
    """SweepRow straight from flat per-coflow CCTs (metrics-only path).

    An empty instance (M == 0) yields an all-zero-metric row rather than
    tripping ``np.quantile`` on an empty array. Public because the fabric
    service and its load harness report stream metrics through the same
    schema (``instance`` then indexes the stream/tick, not a sweep grid).
    """
    return SweepRow(
        instance=idx,
        algorithm=alg,
        scheduling=sched,
        seed=seed,
        weighted_cct=float((weights * ccts).sum()),
        total_cct=float(ccts.sum()),
        p95=tail_quantile(ccts, 0.95),
        p99=tail_quantile(ccts, 0.99),
        makespan=float(ccts.max()) if ccts.size else 0.0,
        n_flows=n_flows,
        wall_s=wall,
    )


def _row_from_schedule(idx: int, alg: str, sched: str, seed: int,
                       s: Schedule, wall: float) -> SweepRow:
    return row_from_ccts(idx, alg, sched, seed, s.inst.weights, s.ccts,
                         len(s.flows), wall)


def run_batch(
    instances: Sequence[Instance | OnlineInstance],
    algorithms: Iterable[str] = ALGORITHMS,
    *,
    seeds: Sequence[int] = (0,),
    schedulings: Iterable[str] = ("work-conserving",),
    pair_seeds: bool = False,
    check: str = "validate",
    workers: int | None = None,
    releases: Sequence[np.ndarray | None] | None = None,
    backend: str = "numpy",
    materialize: str = "full",
) -> ResultTable:
    """Run a whole sweep grid through the batched engine.

    ``instances x algorithms x schedulings x seeds`` is the full grid;
    with ``pair_seeds=True``, ``seeds`` must align with ``instances`` and
    seed ``seeds[i]`` is used only for instance ``i`` (the benchmark
    convention, where the instance-sampling seed doubles as the rand-assign
    seed). The sunflow baselines ignore ``schedulings`` — they always use
    their own coflow-at-a-time policy and are run once per (instance, seed)
    with scheduling recorded as ``"sunflow"``.

    Online grid points: an entry of ``instances`` may be an
    ``OnlineInstance``, and/or ``releases`` may give a per-instance release
    array (aligned with ``instances``; ``None`` entries stay offline, and a
    non-``None`` entry overrides an ``OnlineInstance``'s own releases).
    Those points run ``engine.run_fast_online`` with the same differential
    gating as offline points (oracle = ``online.run_online``).

    ``check``: "validate" (default) runs the independent feasibility
    validator on every schedule (release-respecting for online points);
    "oracle" additionally cross-checks against the legacy per-core scheduler
    (exact agreement, including the assignment-phase core choices); "none"
    skips both.

    ``backend``: assignment-phase backend for every grid point
    (``engine.BACKENDS``) — "numpy" (default, bit-identical to the oracles)
    or "pallas" (tau-aware policy on the TPU kernel).

    ``materialize``: "full" (default) builds ``Schedule`` objects per grid
    point; "metrics" computes ``SweepRow`` metrics straight from the flat
    engine arrays — no ``ScheduledFlow``/``Assignment`` objects at all, the
    production sweep mode at trace scale. Metrics mode requires
    ``check="none"`` (both checkers consume the materialized objects; the
    legacy object-building path stays the oracle and is exercised by
    ``check="oracle"`` sweeps and the differential suites).

    ``workers``: 0 or 1 for in-process serial execution; ``None`` picks a
    sensible default (serial for small grids, one process per CPU otherwise).
    Rows come back in deterministic grid order regardless of worker count.
    ``backend="pallas"`` always runs serially in the calling process: a chip
    belongs to one process, so worker processes could not reach it, and an
    explicit ``workers > 1`` raises ``ValueError``.
    """
    from .engine import BACKENDS

    algorithms = tuple(algorithms)
    schedulings = tuple(schedulings)
    seeds = tuple(seeds)
    unknown = set(algorithms) - set(ALGORITHMS)
    if unknown:
        raise ValueError(f"unknown algorithms {sorted(unknown)}")
    if check not in ("none", "validate", "oracle"):
        raise ValueError(f"unknown check {check!r}")
    if backend not in BACKENDS:
        raise ValueError(f"unknown backend {backend!r}; one of {BACKENDS}")
    if materialize not in ("full", "metrics"):
        raise ValueError(f"unknown materialize {materialize!r}")
    if materialize == "metrics" and check != "none":
        raise ValueError(
            'materialize="metrics" skips schedule objects, so it requires '
            f'check="none" (got check={check!r})')
    if pair_seeds and len(seeds) != len(instances):
        raise ValueError(
            f"pair_seeds=True needs len(seeds) == len(instances), "
            f"got {len(seeds)} vs {len(instances)}")
    if releases is not None and len(releases) != len(instances):
        raise ValueError(
            f"releases must align with instances: "
            f"got {len(releases)} vs {len(instances)}")

    grid = []
    for idx, inst in enumerate(instances):
        rel = None
        if isinstance(inst, OnlineInstance):
            inst, rel = inst.inst, inst.releases
        if releases is not None and releases[idx] is not None:
            rel = np.asarray(releases[idx], dtype=np.float64)
        inst_seeds = (seeds[idx],) if pair_seeds else seeds
        for seed in inst_seeds:
            for alg in algorithms:
                if alg in _SUNFLOW_ALGS:
                    grid.append((idx, inst, rel, alg, "sunflow", seed, check,
                                 backend, materialize))
                else:
                    for sched in schedulings:
                        grid.append((idx, inst, rel, alg, sched, seed, check,
                                     backend, materialize))

    if backend == "pallas":
        if workers is not None and workers > 1:
            raise ValueError(
                f'backend="pallas" runs in the process that holds the chip; '
                f"workers={workers} would start processes that cannot reach it")
        workers = 0
    if workers is None:
        workers = 0 if len(grid) < 4 else min(os.cpu_count() or 1, len(grid), 16)
    if workers and workers > 1 and len(grid) > 1:
        import concurrent.futures as cf
        import multiprocessing as mp

        ctx = mp.get_context(_start_method())
        with cf.ProcessPoolExecutor(max_workers=workers, mp_context=ctx) as ex:
            rows = list(ex.map(_run_one, grid, chunksize=max(1, len(grid) // (4 * workers))))
    else:
        rows = [_run_one(p) for p in grid]
    return ResultTable(rows)

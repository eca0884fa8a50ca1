"""Shared benchmark harness: one paper setting -> normalized metrics table.

All settings run through the batched sweep engine (``repro.core.run_batch``),
so a whole (instances x algorithms) grid is scheduled by the vectorized
engine — optionally across worker processes — and every schedule passes the
independent feasibility validator before its metrics are aggregated.

``emit_json`` writes each section's machine-readable ``BENCH_<name>.json``
artifact (setting, wall-clock, returned metrics) so the perf trajectory is
diffable across PRs; ``benchmarks.run`` wraps every section with it.
"""
from __future__ import annotations

import json
import os

import numpy as np

from repro.core import ALGORITHMS, run_batch, sample_instance, synth_fb_trace

# Paper §V-A rate vectors
IMBALANCED = {3: [10, 20, 30], 4: [5, 10, 20, 25], 5: [5, 5, 10, 15, 25]}
BALANCED = {3: [20, 20, 20], 4: [15, 15, 15, 15], 5: [12, 12, 12, 12, 12]}

_TRACE = None

#: Process count for run_batch; ``benchmarks.run --workers N`` overrides.
DEFAULT_WORKERS: int | None = None


def trace():
    global _TRACE
    if _TRACE is None:
        _TRACE = synth_fb_trace(526, seed=2026)
    return _TRACE


def tick_times(oinst, n_ticks: int) -> np.ndarray:
    """Evenly spaced service-tick grid over an online instance's arrival
    span (one tick at t=0 when every release is 0) — shared by the service
    and fault load harnesses so their streams stay comparable."""
    hi = float(oinst.releases.max()) if oinst.releases.size else 0.0
    if hi <= 0:
        return np.zeros(1)
    return np.linspace(hi / n_ticks, hi, n_ticks)


def _jsonable(x):
    """Recursively coerce numpy scalars/arrays and dataclass-ish payloads."""
    if isinstance(x, dict):
        return {str(k): _jsonable(v) for k, v in x.items()}
    if isinstance(x, (list, tuple)):
        return [_jsonable(v) for v in x]
    if isinstance(x, np.ndarray):
        return [_jsonable(v) for v in x.tolist()]
    if isinstance(x, (np.floating, np.integer, np.bool_)):
        x = x.item()
    if isinstance(x, float) and not np.isfinite(x):
        return repr(x)  # json has no inf/nan
    return x


def emit_json(name: str, payload, wall_s: float, out_dir: str | None = None,
              **meta) -> str:
    """Write ``BENCH_<name>.json`` with a section's metrics; returns the path.

    ``payload`` is whatever the section's ``main`` returned (rows, dicts of
    CCT ratios, speedups); ``meta`` records the setting knobs. Artifacts go
    to ``out_dir`` (default: ``$BENCH_OUT`` or ``benchmarks/out``).
    """
    if out_dir is None:
        out_dir = os.environ.get(
            "BENCH_OUT", os.path.join(os.path.dirname(__file__), "out"))
    os.makedirs(out_dir, exist_ok=True)
    path = os.path.join(out_dir, f"BENCH_{name}.json")
    doc = {"name": name, "wall_s": round(float(wall_s), 3),
           "setting": _jsonable(meta), "data": _jsonable(payload)}
    with open(path, "w") as fh:
        json.dump(doc, fh, indent=1, sort_keys=True)
    return path


def run_setting(*, N=16, M=100, rates=(10, 20, 30), delta=8.0, seeds=(0, 1, 2),
                weight_mode="uniform-int", algorithms=ALGORITHMS,
                check="validate", workers=None) -> dict:
    """Mean normalized weighted CCT (+ tails) over seeds, normalized to OURS.

    One ``run_batch`` call covers the whole (seed x algorithm) grid; the
    sampling seed doubles as the rand-assign seed (``pair_seeds``), matching
    the paper's protocol.
    """
    algorithms = tuple(algorithms)
    insts = [
        sample_instance(trace(), N=N, M=M, rates=list(rates), delta=delta,
                        seed=seed, weight_mode=weight_mode)
        for seed in seeds
    ]
    tab = run_batch(
        insts, algorithms, seeds=tuple(seeds), pair_seeds=True, check=check,
        workers=DEFAULT_WORKERS if workers is None else workers,
    )
    base_alg = "ours" if "ours" in algorithms else algorithms[0]
    agg = {alg: {"w": [], "p95": [], "p99": []} for alg in algorithms}
    for i, _seed in enumerate(seeds):
        base = tab.filter(instance=i, algorithm=base_alg).rows[0]
        for alg in algorithms:
            r = tab.filter(instance=i, algorithm=alg).rows[0]
            agg[alg]["w"].append(r.weighted_cct / base.weighted_cct)
            agg[alg]["p95"].append(r.p95 / base.p95)
            agg[alg]["p99"].append(r.p99 / base.p99)
    return {alg: {k: float(np.mean(v)) for k, v in d.items()}
            for alg, d in agg.items()}


def fmt_row(label: str, res: dict, key: str = "w") -> str:
    cells = " ".join(f"{res[a][key]:6.3f}" for a in ALGORITHMS)
    return f"{label:28s} {cells}"


HEADER = f"{'setting':28s} " + " ".join(f"{a[:6]:>6s}" for a in ALGORITHMS)

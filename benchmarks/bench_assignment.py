"""Assignment-stage benchmark: flat-array front-end vs the dataclass oracle.

PR 1/2 vectorized the scheduling phase, which left Alg. 1's assignment phase
(lines 5-17) — a per-flow Python loop over ``Flow``/``AssignedFlow``
dataclasses — dominating sweep wall-clock at trace scale. This benchmark
times that stage in isolation on the paper's trace grid:

  - legacy stage: ``nonzero_flows`` extraction + ``assign_tau_aware`` (or the
    rho/random baselines) + ``FlowTable.from_assignment`` — exactly what
    ``run_fast`` executed before the flat front-end;
  - flat stage: ``extract_flows`` + ``assign_fast`` — what ``run_fast`` and
    ``run_batch`` execute now.

Choices are asserted bit-identical on every row (the speedup is free of
semantic drift), and the acceptance row is N=32 / M=300 with a >= 5x target.
A metrics-mode vs full-mode ``run_batch`` comparison quantifies what
skipping ``ScheduledFlow``/``Assignment`` materialization buys end to end.
The Pallas kernel path is exercised on the chip by ``chip_smoke.py``.
"""
from __future__ import annotations

import time

import numpy as np

from repro.core import (
    assign_fast,
    assign_random,
    assign_rho_only,
    assign_tau_aware,
    extract_flows,
    order_coflows,
    run_batch,
    sample_instance,
    synth_fb_trace,
)
from repro.core.engine import FlowTable

GRID = [(16, 100), (32, 200), (32, 300)]  # (N, M); last row is the target
TARGET_SPEEDUP = 5.0

_ORACLES = {"tau-aware": assign_tau_aware, "rho-only": assign_rho_only,
            "random": assign_random}


def _time_stage(fn, repeats: int = 3):
    best, out = float("inf"), None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def main(grid=GRID, policies=("tau-aware", "rho-only", "random"),
         workers=None) -> list:
    trace = synth_fb_trace(526, seed=2026)
    rows = []
    print("== Assignment stage: flat-array front-end vs dataclass oracle ==")
    print(f"{'N':>4s} {'M':>5s} {'policy':>10s} {'flows':>7s} "
          f"{'legacy s':>9s} {'flat s':>9s} {'speedup':>8s}")
    target_speedup = None
    for N, M in grid:
        inst = sample_instance(trace, N=N, M=M, rates=[10, 20, 30], delta=8.0,
                               seed=0)
        pi = order_coflows(inst)
        for policy in policies:
            oracle = _ORACLES[policy]

            def legacy_stage():
                a = (oracle(inst, pi, seed=0) if policy == "random"
                     else oracle(inst, pi))
                return FlowTable.from_assignment(a)

            def flat_stage():
                flows = extract_flows(inst, pi)
                return assign_fast(inst, pi, policy, seed=0, flows=flows)

            t_legacy, table = _time_stage(legacy_stage)
            t_flat, choices = _time_stage(flat_stage)
            np.testing.assert_array_equal(choices, table.core)  # no drift
            speedup = t_legacy / t_flat
            rows.append({"N": N, "M": M, "policy": policy,
                         "flows": table.n_flows, "legacy_s": t_legacy,
                         "flat_s": t_flat, "speedup": speedup})
            print(f"{N:4d} {M:5d} {policy:>10s} {table.n_flows:7d} "
                  f"{t_legacy:9.3f} {t_flat:9.3f} {speedup:7.1f}x")
            if (N, M, policy) == (32, 300, "tau-aware"):
                target_speedup = speedup
    if target_speedup is not None:
        verdict = "OK" if target_speedup >= TARGET_SPEEDUP else "MISS"
        print(f"acceptance (N=32, M=300, tau-aware): {target_speedup:.1f}x "
              f"vs >= {TARGET_SPEEDUP:.0f}x target -> {verdict}")

    # End-to-end: what metrics-only materialization buys a sweep.
    N, M = grid[-1]
    inst = sample_instance(trace, N=N, M=M, rates=[10, 20, 30], delta=8.0,
                           seed=0)
    algs = ("ours", "rho-assign", "rand-assign")
    w = 0 if workers is None else workers
    t_full, _ = _time_stage(
        lambda: run_batch([inst], algs, check="none", workers=w), repeats=1)
    t_metrics, _ = _time_stage(
        lambda: run_batch([inst], algs, check="none", workers=w,
                          materialize="metrics"), repeats=1)
    print(f"run_batch N={N} M={M} x {len(algs)} algs: full {t_full:.2f}s vs "
          f"metrics-only {t_metrics:.2f}s -> {t_full/t_metrics:.1f}x")
    return rows


if __name__ == "__main__":
    main()

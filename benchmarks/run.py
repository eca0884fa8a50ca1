"""Benchmark aggregator — one section per paper table/figure plus the
framework-level reports.

  python -m benchmarks.run [--full] [--section NAME]

Default mode keeps wall time modest (fewer seeds / subsets); --full runs the
paper's complete grids; ``--section fault`` (or any other section name) runs
just that section. Every section additionally emits a machine-readable
``BENCH_<name>.json`` artifact (setting, wall-clock, returned metrics) under
``--out`` (default ``benchmarks/out``, override with $BENCH_OUT) so the
performance trajectory is diffable across PRs.
"""
from __future__ import annotations

import argparse
import sys
import time


def _section(name: str, fn, /, trace_dir=None, **kw) -> None:
    """Run one benchmark section and emit its JSON artifact.

    With ``trace_dir`` set, a ``repro.obs`` tracer is installed as the
    process-wide default for the section's duration, so every
    ``FabricManager`` the section builds emits phase spans into
    ``TRACE_<name>.jsonl`` (summarize/diff them with ``python -m
    repro.obs``).
    """
    import os

    from benchmarks import common

    print("#" * 72)
    tracer = prev = None
    if trace_dir is not None:
        from repro.obs.trace import Tracer, set_tracer
        os.makedirs(trace_dir, exist_ok=True)
        tracer = Tracer(os.path.join(trace_dir, f"TRACE_{name}.jsonl"))
        prev = set_tracer(tracer)
    t0 = time.time()
    try:
        payload = fn(**kw)
    finally:
        if tracer is not None:
            from repro.obs.trace import set_tracer
            set_tracer(prev)
            tracer.close()
            print(f"[{name}] trace: {tracer._sink_path} "
                  f"({len(tracer.records)} records)")
    wall = time.time() - t0
    path = common.emit_json(name, payload, wall, **{
        k: v for k, v in kw.items() if isinstance(v, (int, float, str, tuple))
    })
    print(f"[{name}] artifact: {path}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--section", type=str, default=None,
                    help="run only the named section (e.g. fault, service)")
    ap.add_argument("--skip-comm", action="store_true",
                    help="skip the 512-device comm-planner compile")
    ap.add_argument("--workers", type=int, default=None,
                    help="run_batch worker processes for the paper sweeps "
                         "(default: auto; 0 = in-process serial)")
    ap.add_argument("--out", type=str, default=None,
                    help="directory for BENCH_<name>.json artifacts "
                         "(default: $BENCH_OUT or benchmarks/out)")
    ap.add_argument("--trace-dir", type=str, default=None,
                    help="write a TRACE_<section>.jsonl phase trace per "
                         "section (inspect with `python -m repro.obs`)")
    args = ap.parse_args(argv)

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    t0 = time.time()
    from benchmarks import (
        bench_assignment,
        bench_core_scaling,
        bench_fault,
        bench_overload,
        bench_service,
        comm_planner,
        common,
        online_arrivals,
        paper_delta_sensitivity,
        paper_fig4_ablation,
        paper_gamma_w,
        paper_m_scaling,
        paper_n_scaling,
        roofline_report,
    )

    common.DEFAULT_WORKERS = args.workers
    if args.out is not None:
        import os
        os.environ["BENCH_OUT"] = args.out

    sections = [
        ("fig4_ablation", paper_fig4_ablation.main,
         dict(seeds=(0, 1, 2, 3, 4) if args.full else (0, 1, 2))),
        ("delta_sensitivity", paper_delta_sensitivity.main,
         dict(deltas=(2, 4, 6, 8, 10, 12) if args.full else (2, 8, 12),
              seeds=(0, 1, 2) if args.full else (0, 1))),
        ("n_scaling", paper_n_scaling.main,
         dict(ns=(8, 12, 16, 24, 32) if args.full else (8, 16, 32),
              seeds=(0, 1, 2) if args.full else (0, 1))),
        ("m_scaling", paper_m_scaling.main,
         dict(ms=(50, 100, 150, 200, 250) if args.full else (50, 100, 250),
              seeds=(0, 1) if args.full else (0,))),
        ("gamma_w", paper_gamma_w.main,
         dict(seeds=(0, 1) if args.full else (0,))),
        ("online_arrivals", online_arrivals.main,
         dict(seeds=(0, 1) if args.full else (0,))),
        ("core_scaling", bench_core_scaling.main, dict(workers=args.workers)),
        ("assignment", bench_assignment.main, dict(workers=args.workers)),
        ("service", bench_service.main,
         dict(n_ticks=24 if args.full else 16)),
        ("fault", bench_fault.main,
         dict(M=360 if args.full else 240, n_ticks=16)),
        ("overload", bench_overload.main,
         dict(M=400 if args.full else 300, n_ticks=40 if args.full else 30,
              loads=(0.5, 1.0, 1.5, 2.0) if args.full else (0.5, 1.0, 2.0))),
        ("roofline", roofline_report.main, {}),
    ]
    known = [name for name, _fn, _kw in sections] + ["comm_planner"]
    if args.section is not None and args.section not in known:
        ap.error(f"unknown section {args.section!r}; one of {known}")
    for name, fn, kw in sections:
        if args.section is None or args.section == name:
            _section(name, fn, trace_dir=args.trace_dir, **kw)
    if not args.skip_comm and args.section in (None, "comm_planner"):
        print("#" * 72)
        try:
            _section("comm_planner", comm_planner.main,
                     trace_dir=args.trace_dir)
        except Exception as e:  # the compile is heavy; report, don't die
            print(f"[comm_planner] skipped: {e}")
    print("#" * 72)
    print(f"benchmarks done in {time.time()-t0:.1f}s")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())

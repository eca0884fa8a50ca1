"""Observability plane: phase tracing, metrics, trace/bench diff tooling.

Import surface:

- :mod:`repro.obs.clock` — the ONLY sanctioned wall-clock read
  (reprolint RL103 blesses exactly this module path).
- :mod:`repro.obs.trace` — span tracer (``Tracer``/``NULL_TRACER``,
  ``current_tracer``/``set_tracer``), JSONL + Chrome-trace export, the
  bridge that copies spans into an active JAX profiler session, and
  ``compile_count`` (XLA compilations the persistent cache missed).
- :mod:`repro.obs.metrics` — ``MetricsRegistry`` with counters, gauges,
  coverage-honest windowed histograms.
- ``python -m repro.obs`` — summarize/validate/diff traces and
  ``BENCH_*.json`` artifacts (see :mod:`repro.obs.cli`).

This package is pure stdlib + numpy (``compile_count`` imports
``jax.monitoring`` on its first call, made only inside a live span) and
never imported *by* the scheduling core at module level except through
the narrow tracer/clock seams, so tracing off means the scheduler's
behavior (and output) is bit-identical to a build without this package.
"""
from __future__ import annotations

from .metrics import Counter, Gauge, Histogram, MetricsRegistry
from .trace import (NULL_TRACER, NullTracer, Span, Tracer, compile_count,
                    current_tracer, set_tracer, to_chrome_trace)

__all__ = [
    "Counter", "Gauge", "Histogram", "MetricsRegistry",
    "NULL_TRACER", "NullTracer", "Span", "Tracer", "compile_count",
    "current_tracer", "set_tracer", "to_chrome_trace",
]

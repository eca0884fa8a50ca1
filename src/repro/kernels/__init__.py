"""Pallas TPU kernels for the perf-critical hot spots, each with a pure-jnp
oracle (ref.py) and a jit'd public wrapper (ops.py).

  flash_attention  — blocked causal/local GQA attention forward (the model
                     zoo's dominant compute+memory hot spot; removes the S^2
                     score materialization the roofline analysis surfaces).
  coflow_assign    — the paper's tau-aware greedy cross-core assignment
                     (Alg. 1 lines 5-17) with VMEM-resident scheduler state.
"""
from . import ref  # noqa: F401

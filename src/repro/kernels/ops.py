"""Public jit'd wrappers around the Pallas kernels.

On a TPU the calls compile to Mosaic. Anywhere else they raise, unless
``REPRO_PALLAS_INTERPRET=1`` asks for the Pallas interpreter (the test suite
sets it in ``tests/conftest.py``): a kernel never falls back to the
interpreter on its own, so no CPU timing passes for a device run. The
wrapper signatures match the XLA reference paths so models can switch
implementation per-config.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp

from repro.core.effects import effects
from repro.kernels.coflow_assign import coflow_assign_fwd, padded_flows
from repro.kernels.flash_attention import flash_attention_fwd
from repro.obs.trace import current_tracer

__all__ = ["flash_attention", "coflow_assign", "interpret_mode"]


def interpret_mode() -> bool:
    """True when interpret mode was asked for; raises when there is no TPU."""
    if os.environ.get("REPRO_PALLAS_INTERPRET") == "1":
        return True
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"no TPU found (JAX backend is {backend!r}): the Pallas kernels "
            f"run on a TPU, or in the interpreter with "
            f"REPRO_PALLAS_INTERPRET=1")
    return False


def flash_attention(q, k, v, *, causal=True, window=None, softmax_scale=None,
                    q_positions=None, kv_positions=None, kv_valid=None,
                    block_q=512, block_k=512):
    """Self-attention flash kernel (q_len == kv_len, positions implicit).

    The cache-aware arguments (q_positions/kv_positions/kv_valid) are only
    used by the XLA path; the kernel covers the train/prefill self-attention
    hot spot where positions are the trivial iota.
    """
    del q_positions, kv_positions, kv_valid
    sq = q.shape[1]
    bq = min(block_q, sq)
    while sq % bq:
        bq //= 2
    sk = k.shape[1]
    bk = min(block_k, sk)
    while sk % bk:
        bk //= 2
    return flash_attention_fwd(
        q, k, v, causal=causal, window=window, softmax_scale=softmax_scale,
        block_q=max(bq, 1), block_k=max(bk, 1), interpret=interpret_mode())


@effects("trace-emit")
def coflow_assign(fi, fj, sizes, rates, delta, *, n_ports, block_f=256):
    """Tau-aware greedy assignment; returns per-flow core choices (F,) int32.

    Production entry point of the assignment kernel: this is what
    ``core.engine`` dispatches to for ``backend="pallas"`` (flat flow arrays
    from ``coflow.extract_flows``, any integer/float dtype — cast to the
    kernel's int32/fp32 here). Inherits the fp32 precision contract of
    ``coflow_assign_fwd``: choices can diverge from the fp64 oracles on
    near-tie flows at large F; use the numpy backend for bit-reproducibility.

    Traced as ``oneshot/assign/put`` (the casts and transfers) and
    ``oneshot/assign/launch`` (the jitted call, which returns before the
    device finishes).
    """
    tracer = current_tracer()
    with tracer.span("oneshot/assign/put"):
        args = (jnp.asarray(fi, jnp.int32), jnp.asarray(fj, jnp.int32),
                jnp.asarray(sizes, jnp.float32),
                jnp.asarray(rates, jnp.float32))
    with tracer.span("oneshot/assign/launch") as sp:
        if sp.live:
            sp.set(padded_flows=padded_flows(args[0].shape[0], block_f))
        return coflow_assign_fwd(
            *args, float(delta), n_ports=n_ports, block_f=block_f,
            interpret=interpret_mode())

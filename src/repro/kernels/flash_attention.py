"""Blocked causal/local GQA flash-attention forward — Pallas TPU kernel.

TPU-native tiling: grid = (batch, q_heads, q_blocks, kv_blocks) with the
kv-block dimension "arbitrary" (sequential) so the fp32 online-softmax state
(m, l, acc) lives in VMEM scratch and persists across kv steps. Q/K/V/O tiles
are staged HBM->VMEM by BlockSpecs with MXU-aligned (128-multiple) block
shapes; GQA is handled in the K/V index maps (kv_head = q_head // group).

Causal/local-window masking skips fully-masked kv blocks via pl.when, and
applies the elementwise mask only on the (at most two) boundary blocks.

Used as the TPU path of ``repro.models.attention.attend`` (self-attention,
q_len == kv_len); validated in interpret mode against ``ref.attention_ref``
over shape/dtype sweeps in tests/test_kernels_attention.py.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

__all__ = ["flash_attention_fwd"]

NEG_INF = -0.7 * float(jnp.finfo(jnp.float32).max)


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
               scale: float, causal: bool, window: int | None,
               bq: int, bk: int, n_kv: int):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q_start = iq * bq
    k_start = ik * bk

    # Block-level visibility: skip kv blocks wholly in the future (causal)
    # or wholly before the window.
    visible = jnp.asarray(True)
    if causal:
        visible = jnp.logical_and(visible, k_start <= q_start + bq - 1)
    if window is not None:
        visible = jnp.logical_and(visible, k_start + bk - 1 > q_start - window)

    @pl.when(visible)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32) * scale  # (bq, dh)
        k = k_ref[0, 0].astype(jnp.float32)  # (bk, dh)
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (bq, bk)
        qp = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kp = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), bool)
        if causal:
            mask &= kp <= qp
        if window is not None:
            mask &= kp > qp - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_ref[...]  # (bq, 1)
        l_prev = l_ref[...]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)  # (bq, bk)
        alpha = jnp.exp(m_prev - m_new)  # (bq, 1)
        l_new = alpha * l_prev + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())))
        m_ref[...] = m_new
        l_ref[...] = l_new

    @pl.when(ik == n_kv - 1)
    def _finalize():
        l = l_ref[...]
        safe = jnp.where(l == 0.0, 1.0, l)
        o_ref[0, 0] = (acc_ref[...] / safe).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "softmax_scale", "block_q", "block_k",
                     "interpret"),
)
def flash_attention_fwd(
    q: jax.Array,  # (B, Sq, H, Dh)
    k: jax.Array,  # (B, Sk, KVH, Dh)
    v: jax.Array,
    *,
    causal: bool = True,
    window: int | None = None,
    softmax_scale: float | None = None,
    block_q: int = 512,
    block_k: int = 512,
    interpret: bool = False,
) -> jax.Array:
    b, sq, h, dh = q.shape
    _, sk, kvh, _ = k.shape
    assert h % kvh == 0
    group = h // kvh
    scale = softmax_scale if softmax_scale is not None else dh**-0.5
    bq = min(block_q, sq)
    bk = min(block_k, sk)
    assert sq % bq == 0 and sk % bk == 0, (sq, bq, sk, bk)
    n_q, n_kv = sq // bq, sk // bk

    # (B, S, H, D) -> (B, H, S, D) blocks
    qt = q.transpose(0, 2, 1, 3)
    kt = k.transpose(0, 2, 1, 3)
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _fa_kernel, scale=scale, causal=causal, window=window,
        bq=bq, bk=bk, n_kv=n_kv)

    out = pl.pallas_call(
        kernel,
        grid=(b, h, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, bq, dh), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b_, h_, iq, ik, g=group: (b_, h_ // g, ik, 0)),
            pl.BlockSpec((1, 1, bk, dh),
                         lambda b_, h_, iq, ik, g=group: (b_, h_ // g, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, dh), lambda b_, h_, iq, ik: (b_, h_, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((b, h, sq, dh), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, dh), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)  # back to (B, Sq, H, Dh)

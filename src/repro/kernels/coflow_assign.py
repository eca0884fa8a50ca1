"""Tau-aware greedy cross-core flow assignment (Alg. 1 lines 5-17) — Pallas TPU.

TPU adaptation of the paper's assignment hot loop (the O(F*K) inner loop that
dominates control-plane latency at datacenter scale, F up to ~10^6 flows):

  - Scheduler state is pinned in VMEM across the whole run: per-core row/col
    loads and tau counts (6 x (K, N) fp32: each load is two words), the
    nonzero bitmap flattened to (K, N*N) fp32 (tau increments only on first
    traffic per (i,j,k)), and the running per-core bound (2 x (K, 1)). The
    bitmap dominates: 4*K*N^2 bytes, and
    the per-flow masked reads and updates of it need more again in
    temporaries. Compiled for a TPU v5e at K=8, N=384 fits Mosaic's default
    16 MiB scoped VMEM and N=512 does not; the raised ``VMEM_LIMIT_BYTES``
    (64 MiB of the chip's 128 MiB) takes N to ``MAX_PORTS`` = 832, and
    N=864 is refused.
  - Flows stream from HBM in blocks via BlockSpecs (the grid dimension is
    sequential, so state persists across blocks). The per-flow scalars
    (ingress, egress, size) and delta sit in SMEM: the loop reads them at a
    dynamic index, which a VMEM vector load cannot do.
  - The greedy chain is inherently sequential (each choice feeds the next
    bound) — that chain IS the algorithm, so the inner fori_loop is a
    sequential loop over the flow block, with each step fully vectorized
    across cores and ports via one-hot masks instead of scatters
    (TPU-native: VPU selects, no dynamic scatter). Every vector value is 2-D
    — per-core columns (K, 1), port rows (1, N), the bitmap row (1, N*N) —
    because Mosaic lays out rank-2 vectors only.
  - Loads, completion bounds and their comparisons are two-word fp32
    (hi + lo, ~48 significand bits), not fp32. The greedy keeps the per-core
    bounds in near-lockstep, so at the FB2010 deployment (N=150, 443,943
    flows) one-word fp32 state first disagrees with the fp64 oracle where two
    bounds differ by 6e-8 relative, and the cascade then moves 59% of the
    choices; two-word state agrees on every flow there.

Returns the same choices as the numpy oracle (ref.assign_ref) bit-for-bit in
argmin tie-breaking (lowest core index).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
import jax.experimental.pallas.tpu as pltpu

__all__ = ["coflow_assign_fwd", "padded_flows", "MAX_CORES", "MAX_PORTS",
           "VMEM_LIMIT_BYTES"]

#: Largest core count the VMEM budget below is sized for.
MAX_CORES = 8
#: Largest port count N that compiles for a TPU v5e at ``MAX_CORES`` cores
#: (fewer cores pad to the same 8 sublanes, so it holds for every K <= 8).
MAX_PORTS = 832
#: Scoped-VMEM budget handed to Mosaic (the default scoped limit is 16 MiB).
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


# Two-word fp32 ("float-float") arithmetic: a value is the unevaluated sum
# hi + lo with |lo| <= ulp(hi) / 2, about 48 significand bits. The building
# blocks are the error-free transformations of Knuth (two_sum), Dekker
# (fast_two_sum, two_prod) and Veltkamp (_split); they need round-to-nearest
# fp32 adds and multiplies, and no reassociation.
_SPLIT = 4097.0  # 2**12 + 1: splits a 24-bit significand into two halves


def _two_sum(a, b):
    s = a + b
    bb = s - a
    return s, (a - (s - bb)) + (b - bb)


def _fast_two_sum(a, b):  # |a| >= |b|
    s = a + b
    return s, b - (s - a)


def _split(a):
    c = _SPLIT * a
    hi = c - (c - a)
    return hi, a - hi


def _two_prod(a, b):
    p = a * b
    ah, al = _split(a)
    bh, bl = _split(b)
    return p, ((ah * bh - p) + ah * bl + al * bh) + al * bl


def _ff_add(ah, al, bh, bl):
    s, e = _two_sum(ah, bh)
    return _fast_two_sum(s, e + (al + bl))


def _ff_div(ah, al, b):
    """(ah + al) / b for an fp32 ``b``: one quotient plus its correction."""
    q = ah / b
    p, pe = _two_prod(q, b)
    return _fast_two_sum(q, (((ah - p) - pe) + al) / b)


def _ff_max(ah, al, bh, bl):
    a_wins = (ah > bh) | ((ah == bh) & (al > bl))
    return jnp.where(a_wins, ah, bh), jnp.where(a_wins, al, bl)


def _assign_kernel(fi_ref, fj_ref, sz_ref, delta_ref, rates_ref, out_ref,
                   row_hi, row_lo, col_hi, col_lo, row_tau, col_tau, nz,
                   bound_hi, bound_lo, *, bf: int, k_cores: int, n_ports: int):
    step = pl.program_id(0)

    @pl.when(step == 0)
    def _init():
        for ref in (row_hi, row_lo, col_hi, col_lo, row_tau, col_tau, nz,
                    bound_hi, bound_lo):
            ref[...] = jnp.zeros_like(ref)

    rates = rates_ref[...]  # (K, 1)
    delta = jnp.full((k_cores, 1), delta_ref[0, 0], jnp.float32)
    zero = jnp.zeros((k_cores, 1), jnp.float32)
    iota_n = jax.lax.broadcasted_iota(jnp.int32, (1, n_ports), 1)
    iota_nn = jax.lax.broadcasted_iota(jnp.int32, (1, n_ports * n_ports), 1)
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (k_cores, 1), 0)
    iota_f = jax.lax.broadcasted_iota(jnp.int32, (1, bf), 1)

    def at(ref, onehot):  # (K, 1) column of a state array: one nonzero term
        return jnp.sum(ref[...] * onehot, axis=1, keepdims=True)

    def body(t, out_blk):
        i = fi_ref[0, t]
        j = fj_ref[0, t]
        d = jnp.full((k_cores, 1), sz_ref[0, t], jnp.float32)
        oh_i = (iota_n == i).astype(jnp.float32)  # (1, N)
        oh_j = (iota_n == j).astype(jnp.float32)
        oh_ij = (iota_nn == i * n_ports + j).astype(jnp.float32)  # (1, N*N)
        valid = jnp.where(sz_ref[0, t] >= 0.0, 1.0, 0.0)  # padded tail: -1

        # was (i, j) already nonzero on core k?
        new = 1.0 - jnp.minimum(at(nz, oh_ij), 1.0)
        # row / col load with this flow, then (load / rate + tau * delta)
        ri = _ff_add(at(row_hi, oh_i), at(row_lo, oh_i), d, zero)
        cj = _ff_add(at(col_hi, oh_j), at(col_lo, oh_j), d, zero)
        li = _ff_add(*_ff_div(*ri, rates),
                     *_two_prod(at(row_tau, oh_i) + new, delta))
        lj = _ff_add(*_ff_div(*cj, rates),
                     *_two_prod(at(col_tau, oh_j) + new, delta))
        c_hi, c_lo = _ff_max(bound_hi[...], bound_lo[...], *_ff_max(*li, *lj))
        # argmin with ties to the lowest core: the least index at the min
        m_hi = jnp.min(c_hi, axis=0, keepdims=True)
        m_lo = jnp.min(jnp.where(c_hi == m_hi, c_lo, jnp.inf), axis=0,
                       keepdims=True)
        at_min = (c_hi == m_hi) & (c_lo == m_lo)
        kstar = jnp.min(jnp.where(at_min, iota_k, k_cores), axis=0,
                        keepdims=True)  # (1, 1)
        oh_k = (iota_k == kstar).astype(jnp.float32) * valid  # (K, 1)

        # commit: only row i / col j / cell (i, j) of core kstar change
        row = oh_k * oh_i > 0.0
        col = oh_k * oh_j > 0.0
        row_hi[...] = jnp.where(row, ri[0], row_hi[...])
        row_lo[...] = jnp.where(row, ri[1], row_lo[...])
        col_hi[...] = jnp.where(col, cj[0], col_hi[...])
        col_lo[...] = jnp.where(col, cj[1], col_lo[...])
        row_tau[...] = row_tau[...] + (new * oh_k) * oh_i
        col_tau[...] = col_tau[...] + (new * oh_k) * oh_j
        nz[...] = jnp.maximum(nz[...], oh_k * oh_ij)
        # cand[kstar] = max(bound, li, lj) IS the post-commit bound of kstar
        # (loads are non-decreasing); other cores keep their bound.
        take = oh_k > 0.0
        bound_hi[...] = jnp.where(take, c_hi, bound_hi[...])
        bound_lo[...] = jnp.where(take, c_lo, bound_lo[...])
        return jnp.where(iota_f == t, kstar, out_blk)

    out_ref[...] = jax.lax.fori_loop(0, bf, body,
                                     jnp.zeros((1, bf), jnp.int32))


def padded_flows(f: int, block_f: int) -> int:
    """The kernel's flow count: ``f`` padded to whole blocks of
    ``min(block_f, f)`` flows."""
    return f + (-f) % min(block_f, f) if f else 0


@functools.partial(jax.jit,
                   static_argnames=("n_ports", "block_f", "interpret"))
def coflow_assign_fwd(
    fi: jax.Array,  # (F,) int32 ingress ports (global flow order)
    fj: jax.Array,  # (F,) int32 egress ports
    sizes: jax.Array,  # (F,) float32 (padded tail entries = -1)
    rates: jax.Array,  # (K,) float32
    delta: float,
    *,
    n_ports: int,
    block_f: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Returns choices (F,) int32 — the core assigned to each flow.

    Raises ``ValueError`` for ``n_ports > MAX_PORTS`` or more than
    ``MAX_CORES`` cores: the VMEM-resident state would not fit.

    Precision contract: inputs are **fp32** (sizes, rates, delta) and the
    kernel keeps loads and bounds in two fp32 words (~48 significand bits;
    tau counts are exact in fp32), while the reference oracles
    (``kernels.ref.assign_ref``, ``core.lower_bounds.CoreState``) accumulate
    in fp64. The greedy argmin is a chain of near-ties, so rounding can flip
    a tie decision — and, because every choice feeds the next prefix state,
    one flipped choice can cascade. In practice:

      - choices agree exactly with ``assign_ref`` evaluated at the same
        fp32-cast inputs (the differential grid in
        tests/test_kernels_assign.py asserts bit-equality, including bounds
        that differ below fp32 resolution);
      - a divergence needs two cores' bounds equal to ~1e-14 relative; the
        gate allows ``max(1, ceil(0.03 F))`` of them, and the slow-marked
        large-F stress test bounds the choice-agreement rate (>97%) and the
        induced end-to-end CCT gap (<2% weighted-CCT drift).

    Callers needing bit-reproducibility against the paper's fp64 pipeline
    (e.g. ``run_batch(check="oracle")`` sweeps) should use the numpy backend;
    ``engine.cross_check(backend="pallas")`` gates this kernel against
    ``assign_ref`` at fp32 inputs and replays the legacy scheduler on the
    kernel's own choices.
    """
    k_cores = rates.shape[0]
    if n_ports > MAX_PORTS:
        raise ValueError(
            f"n_ports={n_ports} exceeds MAX_PORTS={MAX_PORTS}: the (K, N*N) "
            f"bitmap would not fit the {VMEM_LIMIT_BYTES >> 20} MiB VMEM "
            f"budget of the assignment kernel")
    if k_cores > MAX_CORES:
        raise ValueError(
            f"{k_cores} cores exceed MAX_CORES={MAX_CORES} of the assignment "
            f"kernel's VMEM budget")
    f = fi.shape[0]
    if f == 0:
        # An empty flow list would make bf = 0 and a zero-size BlockSpec,
        # which pallas_call rejects; there is nothing to assign.
        return jnp.zeros((0,), jnp.int32)
    bf = min(block_f, f)
    pad = padded_flows(f, block_f) - f
    if pad:
        fi = jnp.concatenate([fi, jnp.zeros((pad,), fi.dtype)])
        fj = jnp.concatenate([fj, jnp.zeros((pad,), fj.dtype)])
        sizes = jnp.concatenate([sizes, -jnp.ones((pad,), sizes.dtype)])
    nb = (f + pad) // bf

    kernel = functools.partial(_assign_kernel, bf=bf, k_cores=k_cores,
                               n_ports=n_ports)
    flow_block = pl.BlockSpec((1, bf), lambda s: (0, s),
                              memory_space=pltpu.SMEM)
    out = pl.pallas_call(
        kernel,
        name="coflow_assign",
        grid=(nb,),
        in_specs=[
            flow_block,  # fi
            flow_block,  # fj
            flow_block,  # sizes
            pl.BlockSpec((1, 1), lambda s: (0, 0), memory_space=pltpu.SMEM),
            pl.BlockSpec((k_cores, 1), lambda s: (0, 0)),  # rates column
        ],
        out_specs=pl.BlockSpec((1, bf), lambda s: (0, s)),
        out_shape=jax.ShapeDtypeStruct((1, f + pad), jnp.int32),
        scratch_shapes=[
            *[pltpu.VMEM((k_cores, n_ports), jnp.float32)] * 6,  # loads, tau
            pltpu.VMEM((k_cores, n_ports * n_ports), jnp.float32),  # nz
            *[pltpu.VMEM((k_cores, 1), jnp.float32)] * 2,  # bound hi, lo
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
    )(fi[None, :], fj[None, :], sizes[None, :].astype(jnp.float32),
      jnp.full((1, 1), delta, jnp.float32),
      rates[:, None].astype(jnp.float32))
    return out[0, :f]

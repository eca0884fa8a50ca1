"""Property-based tests (hypothesis) on system invariants."""
import numpy as np
import pytest

pytest.importorskip(
    "hypothesis", reason="property tests need the hypothesis dev extra")
from hypothesis import given, settings, strategies as st

from repro.core import (
    ALGORITHMS,
    Coflow,
    Instance,
    check_lemma1,
    check_theorem1,
    run,
    validate,
)


@st.composite
def instances(draw):
    M = draw(st.integers(1, 6))
    N = draw(st.integers(2, 8))
    K = draw(st.integers(1, 4))
    rng = np.random.default_rng(draw(st.integers(0, 2**31)))
    coflows = []
    for cid in range(M):
        D = rng.exponential(10, (N, N)) * (rng.random((N, N)) < 0.5)
        if not D.any():
            D[rng.integers(N), rng.integers(N)] = 1.0
        coflows.append(Coflow(cid=cid, demand=D,
                              weight=float(rng.integers(1, 10))))
    rates = rng.uniform(1.0, 30.0, K)
    delta = float(rng.uniform(0.0, 10.0))
    return Instance(coflows=tuple(coflows), rates=rates, delta=delta)


@settings(max_examples=25, deadline=None)
@given(instances(), st.sampled_from(ALGORITHMS))
def test_every_algorithm_produces_feasible_schedules(inst, alg):
    """Port exclusivity, non-preemption, demand conservation, CCT
    consistency, and Lemma 1 hold for EVERY algorithm on random instances."""
    s = run(inst, alg, seed=0)
    validate(s)
    check_lemma1(s)


@settings(max_examples=15, deadline=None)
@given(instances())
def test_theorem1_certificate_random(inst):
    s = run(inst, "ours")
    validate(s)
    check_theorem1(s)


@settings(max_examples=15, deadline=None)
@given(instances())
def test_assignment_conserves_demand_exactly(inst):
    """Sum of per-core assignments equals the original demand matrices."""
    from repro.core import assign_tau_aware, order_coflows

    pi = order_coflows(inst)
    a = assign_tau_aware(inst, pi)
    for m_pos in range(inst.M):
        per_core = a.per_core_demand(m_pos)
        np.testing.assert_allclose(
            per_core.sum(axis=0), inst.coflows[int(pi[m_pos])].demand,
            atol=1e-9)


def test_analyzer_slice_closure():
    """Fusion params reaching dynamic-slice through pass-through ops are
    charged at sliced size (scan-body pattern)."""
    import jax
    import jax.numpy as jnp
    from repro.analysis.hlo import analyze_hlo

    def f(xs):
        def body(c, x):
            return c + jnp.sum(jnp.tanh(x)), None
        out, _ = jax.lax.scan(body, jnp.float32(0), xs)
        return out

    xs = jax.ShapeDtypeStruct((8192, 32, 32), jnp.float32)
    comp = jax.jit(f).lower(xs).compile()
    a = analyze_hlo(comp.as_text())
    # full array is 32 MiB; per-trip slice is 4 KiB. Naive charging would be
    # 8192 trips x 32 MiB = 256 GiB; slice-aware must stay near real traffic.
    assert a.hbm_bytes < 2 * 2**30, f"{a.hbm_bytes/2**30:.1f} GiB charged"

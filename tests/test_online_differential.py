"""Differential-testing harness for the ONLINE scheduling path.

Mirrors tests/test_engine_differential.py for the arrival model: on
randomized instances x arrival patterns, ``engine.run_fast_online`` must be
indistinguishable from the ``online.run_online`` reference oracle
(per-coflow CCTs and per-flow establishment times, bit-exact in practice),
and every schedule must pass the independent release-respecting validator.
Also pins the offline reduction: with all releases forced to 0 the online
engine reproduces the offline engine bit-for-bit, and online ``run_batch``
grids get the same gating as offline ones.
"""
import numpy as np
import pytest

from repro.core import (
    ALGORITHMS,
    Coflow,
    Instance,
    OnlineInstance,
    run_batch,
    run_fast,
    run_fast_online,
    sample_instance,
    synth_fb_trace,
    validate,
)
from repro.core.engine import cross_check_online

N_RANDOM_INSTANCES = 44  # acceptance floor is 40
ARRIVAL_PATTERNS = ("uniform", "bursty")


def _random_instance(trial: int) -> Instance:
    """Randomized instance; regimes rotate with the trial index (same scheme
    as the offline differential suite, different seed stream)."""
    rng = np.random.default_rng(7000 + trial)
    M = int(rng.integers(1, 9))
    N = int(rng.integers(2, 11))
    K = int(rng.integers(1, 6))
    sparsity = float(rng.uniform(0.1, 0.9))
    coflows = []
    for cid in range(M):
        D = rng.exponential(10, (N, N)) * (rng.random((N, N)) < sparsity)
        if not D.any():
            D[rng.integers(N), rng.integers(N)] = float(rng.exponential(10) + 0.1)
        coflows.append(Coflow(cid=cid, demand=D, weight=float(rng.integers(1, 10))))
    if trial % 3 == 0:
        rates = np.full(K, float(rng.uniform(5.0, 20.0)))   # homogeneous
    else:
        rates = np.sort(rng.uniform(1.0, 30.0, K))          # heterogeneous
    delta = 0.0 if trial % 5 == 0 else float(rng.uniform(0.0, 10.0))
    return Instance(coflows=tuple(coflows), rates=rates, delta=delta)


def _releases(inst: Instance, pattern: str, trial: int) -> np.ndarray:
    """Arrival times. ``uniform`` spreads arrivals over a span comparable to
    the workload; ``bursty`` releases coflows in simultaneous batches (exact
    float ties — exercises same-time-arrival WSPT ordering and release
    events colliding with each other)."""
    rng = np.random.default_rng(9000 + trial)
    span = float(inst.delta * 4 + 10.0) * max(inst.M, 1)
    if pattern == "uniform":
        return rng.uniform(0, span, inst.M)
    if pattern == "bursty":
        batch_times = rng.uniform(0, span, max(1, inst.M // 3 + 1))
        return batch_times[rng.integers(0, len(batch_times), inst.M)]
    raise ValueError(pattern)


@pytest.mark.parametrize("pattern", ARRIVAL_PATTERNS)
@pytest.mark.parametrize("trial", range(N_RANDOM_INSTANCES))
def test_online_engine_matches_oracle_randomized(trial, pattern):
    """Engine == oracle on one random (instance, arrival pattern) point.

    Every point checks the paper algorithm plus a rotating baseline
    algorithm — over the whole grid all 5 algorithms are covered many times
    over.
    """
    inst = _random_instance(trial)
    oinst = OnlineInstance(inst=inst, releases=_releases(inst, pattern, trial))
    cross_check_online(oinst, "ours", seed=trial)
    other = [a for a in ALGORITHMS if a != "ours"][trial % 4]
    cross_check_online(oinst, other, seed=trial)


@pytest.mark.parametrize("trial", range(0, N_RANDOM_INSTANCES, 7))
def test_online_zero_releases_match_offline_engine_bitwise(trial):
    """releases = 0 forces the online engine onto the offline schedule."""
    inst = _random_instance(trial)
    oinst = OnlineInstance(inst=inst, releases=np.zeros(inst.M))
    for alg in ALGORITHMS:
        on = run_fast_online(oinst, alg, seed=trial)
        off = run_fast(inst, alg, seed=trial)
        assert np.array_equal(on.ccts, off.ccts), alg
        assert on.flows == off.flows, alg


@pytest.mark.slow
def test_online_engine_matches_oracle_trace_instance():
    """A realistic trace-driven arrival grid (heavier than the random grid)."""
    trace = synth_fb_trace(200, seed=7)
    inst = sample_instance(trace, N=16, M=60, rates=[10, 20, 30], delta=8.0,
                           seed=3)
    span = float(run_fast(inst, "ours").ccts.max())
    for comp in (0.5, 1.5):
        for pattern in ARRIVAL_PATTERNS:
            rng = np.random.default_rng(int(comp * 10))
            rel = (np.sort(rng.uniform(0, span * comp, inst.M))
                   if pattern == "uniform"
                   else _releases(inst, pattern, int(comp * 10)))
            oinst = OnlineInstance(inst=inst, releases=rel)
            for alg in ALGORITHMS:
                cross_check_online(oinst, alg, seed=3)


# --------------------------------------------------------------- run_batch

def test_run_batch_online_grid_gating():
    """OnlineInstance entries run the online engine under oracle gating."""
    insts = [_random_instance(t) for t in (1, 2)]
    oinsts = [OnlineInstance(inst=i, releases=_releases(i, "uniform", t))
              for t, i in enumerate(insts)]
    tab = run_batch(oinsts, ALGORITHMS, seeds=(0,), check="oracle",
                    workers=0)
    assert len(tab) == 2 * len(ALGORITHMS)
    # rows match a direct engine run
    for idx, oi in enumerate(oinsts):
        row = tab.filter(instance=idx, algorithm="ours",
                         scheduling="work-conserving").rows[0]
        s = run_fast_online(oi, "ours", seed=0)
        assert row.weighted_cct == pytest.approx(s.total_weighted_cct, abs=1e-9)


def test_run_batch_releases_kwarg_and_mixed_grid():
    """`releases=` aligns with instances; None entries stay offline."""
    insts = [_random_instance(t) for t in (3, 4)]
    rel = _releases(insts[1], "uniform", 4)
    tab = run_batch(insts, ("ours",), seeds=(0,), check="oracle", workers=0,
                    releases=[None, rel])
    off = run_fast(insts[0], "ours")
    on = run_fast_online(OnlineInstance(inst=insts[1], releases=rel), "ours")
    assert tab.rows[0].weighted_cct == pytest.approx(off.total_weighted_cct)
    assert tab.rows[1].weighted_cct == pytest.approx(on.total_weighted_cct)
    with pytest.raises(ValueError, match="releases"):
        run_batch(insts, ("ours",), releases=[None])


def test_run_batch_online_parallel_matches_serial():
    insts = [OnlineInstance(inst=_random_instance(t),
                            releases=_releases(_random_instance(t), "bursty", t))
             for t in (5, 6)]
    kw = dict(seeds=(0,), check="validate")
    serial = run_batch(insts, ("ours", "rand-sunflow"), workers=0, **kw)
    parallel = run_batch(insts, ("ours", "rand-sunflow"), workers=2, **kw)
    for a, b in zip(serial, parallel):
        assert (a.instance, a.algorithm) == (b.instance, b.algorithm)
        assert a.weighted_cct == b.weighted_cct


def test_validator_rejects_release_violation():
    """The independent validator really checks release respect."""
    inst = _random_instance(8)
    rel = _releases(inst, "uniform", 8)
    s = run_fast_online(OnlineInstance(inst=inst, releases=rel), "ours")
    validate(s, releases=rel)
    # shift one coflow's release past its first establishment -> must fail
    bad = rel.copy()
    f0 = s.flows[0]
    bad[int(s.pi[f0.coflow])] = f0.t_establish + 1.0
    with pytest.raises(AssertionError, match="release"):
        validate(s, releases=bad)


def test_online_instance_validation():
    inst = _random_instance(0)
    with pytest.raises(ValueError, match="shape"):
        OnlineInstance(inst=inst, releases=np.zeros(inst.M + 1))
    with pytest.raises(ValueError, match=">= 0"):
        OnlineInstance(inst=inst, releases=np.full(inst.M, -1.0))

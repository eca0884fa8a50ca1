"""The work-conserving event loop against a plain sequential priority scan.

``_event_loop`` (``guard=False``) picks each event's starts from the freed
resources' pending lists. Its times must be bit-identical to the scan it
stands for: at every event time, walk the pending flows in priority (index)
order and start each one whose release has come and whose two resources
are free, then move to the next time a resource frees or a flow is
released. The reference below does exactly that, in plain Python floats,
sharing nothing with the engine.
"""
from __future__ import annotations

import math

import numpy as np
import pytest

from repro.core.engine import LoopCounts, _event_loop


def _sequential(rin, rout, srv, delta, n_res, t0=0.0, release=None,
                free_in0=None, free_out0=None):
    rin, rout, srv = rin.tolist(), rout.tolist(), srv.tolist()
    dl = delta.tolist() if np.ndim(delta) else [float(delta)] * len(rin)
    rel = None if release is None else release.tolist()
    free_in = [t0] * n_res if free_in0 is None else free_in0.tolist()
    free_out = [t0] * n_res if free_out0 is None else free_out0.tolist()
    t_est = [-1.0] * len(rin)
    pending = list(range(len(rin)))
    t = t0
    while pending:
        waiting = []
        for f in pending:
            if ((rel is None or rel[f] <= t) and free_in[rin[f]] <= t
                    and free_out[rout[f]] <= t):
                tc = (t + dl[f]) + srv[f]
                free_in[rin[f]] = free_out[rout[f]] = tc
                t_est[f] = t
            else:
                waiting.append(f)
        pending = waiting
        if pending:
            ahead = [v for v in free_in + free_out if t < v < math.inf]
            if rel is not None:
                ahead += [rel[f] for f in pending if rel[f] > t]
            t = min(ahead)
    return np.array(t_est)


def _flows(rng, F, K, N, n_in=None):
    core = rng.integers(0, K, F)
    fi = rng.integers(0, n_in or N, F)
    fj = rng.integers(0, N, F)
    rates = np.array([10.0, 20.0, 25.0, 40.0])[:K]
    return core, core * N + fi, core * N + fj, rates


def _case(kind, seed):
    """(event-loop arguments, keyword arguments) of one scenario."""
    rng = np.random.default_rng([seed, len(kind)])
    K, N, F = 2, 6, 300
    kw = {}
    if kind == "long_lists":
        # N = 128 egress ports, 6 ingress ports: ingress lists of ~100
        K, N, F = 1, 128, 600
        core, rin, rout, rates = _flows(rng, F, K, N, n_in=6)
    else:
        core, rin, rout, rates = _flows(rng, F, K, N)
    if kind == "simultaneous":
        # whole-number service times: many flows end at one instant
        srv = rng.integers(1, 4, F).astype(np.float64)
    else:
        srv = rng.uniform(1.0, 60.0, F) / rates[core]
    delta = 8.0
    if kind == "per_flow_delta":
        delta = np.array([8.0, 3.5])[core]
    if kind == "release_ties":
        # releases on a coarse grid: many flows released at one time
        kw["release"] = np.round(rng.uniform(0.0, 80.0, F) / 10.0) * 10.0
    if kind == "horizons":
        # committed circuits from earlier ticks, and a failed core's
        # resources (+inf) that no flow uses
        K = 3
        t0 = 25.0
        n_res = K * N
        free_in0 = np.where(rng.random(n_res) < 0.5,
                            rng.uniform(0.0, 60.0, n_res), 0.0)
        free_out0 = np.where(rng.random(n_res) < 0.5,
                             rng.uniform(0.0, 60.0, n_res), 0.0)
        free_in0[2 * N:] = free_out0[2 * N:] = np.inf
        kw.update(t0=t0, free_in0=free_in0, free_out0=free_out0,
                  release=t0 + np.round(rng.uniform(0.0, 40.0, F)))
    return (rin, rout, srv, core, delta, K * N, N), kw


KINDS = ["offline", "release_ties", "horizons", "per_flow_delta",
         "simultaneous", "long_lists"]


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("kind", KINDS)
def test_times_equal_the_sequential_scan(kind, seed):
    (rin, rout, srv, core, delta, n_res, n_ports), kw = _case(kind, seed)
    counts = LoopCounts()
    got = _event_loop(rin, rout, srv, core, delta, n_res, n_ports,
                      counts=counts, **kw)
    ref = _sequential(rin, rout, srv, delta, n_res, **kw)
    assert (got >= kw.get("t0", 0.0)).all()
    assert np.array_equal(got, ref)
    assert counts.events > 0


def test_cases_exercise_what_they_name():
    """Each scenario really has the feature it is named for."""
    (rin, rout, srv, _c, delta, _n, _p), kw = _case("simultaneous", 0)
    tc = (_sequential(rin, rout, srv, delta, _n) + delta) + srv
    assert np.unique(tc, return_counts=True)[1].max() >= 5
    (_ri, _ro, _s, _c, _d, _n, _p), kw = _case("release_ties", 0)
    assert np.unique(kw["release"], return_counts=True)[1].max() >= 10
    (rin, _ro, _s, _c, _d, _n, _p), kw = _case("long_lists", 0)
    assert _p >= 128 and np.bincount(rin).max() >= 80
    (_ri, _ro, _s, _c, _d, _n, _p), kw = _case("horizons", 0)
    ahead = kw["free_in0"] > kw["t0"]
    assert ahead.any() and np.isinf(kw["free_in0"]).any()

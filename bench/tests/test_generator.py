"""The benchmark's copy of the traffic generator still matches the
program's ``core.trace`` number for number."""
import numpy as np
import pytest

from yardstick import fbtrace


@pytest.fixture(scope="module")
def traces():
    from repro.core import synth_fb_trace

    return synth_fb_trace(526, seed=2026), fbtrace.synth_fb_trace(526, seed=2026)


def test_trace_is_identical(traces):
    prog, ours = traces
    assert len(prog) == len(ours) == 526
    for a, b in zip(prog, ours):
        assert (a.cid, a.arrival_ms, a.mappers, a.reducers, a.reducer_mb) == \
            (b.cid, b.arrival_ms, b.mappers, b.reducers, b.reducer_mb)


@pytest.mark.parametrize("n_ports,n_coflows,rates", [
    (150, 20, (5.0, 10.0, 20.0, 25.0)),
    (16, 100, (10.0, 20.0, 30.0)),
])
@pytest.mark.parametrize("seed", [0, 1, 7, 2**31 + 5])
def test_sample_is_identical(traces, n_ports, n_coflows, rates, seed):
    from repro.core import sample_instance

    prog, ours = traces
    inst = sample_instance(prog, N=n_ports, M=n_coflows, rates=list(rates),
                           delta=8.0, seed=seed)
    b, selected, pick = fbtrace.sample_backlog(
        ours, n_ports=n_ports, n_coflows=n_coflows, seed=seed)
    assert np.array_equal(np.stack([c.demand for c in inst.coflows]),
                          b.demands)
    assert np.array_equal(inst.weights, b.weights)
    assert selected.size == n_ports and pick.size == n_coflows


def test_redraw_keeps_sizes_and_changes_numbers(traces):
    _, ours = traces
    b, selected, pick = fbtrace.sample_backlog(ours, n_ports=150,
                                               n_coflows=20, seed=3)
    r1 = fbtrace.redraw_backlog(ours, selected, pick, np.random.default_rng(1))
    r2 = fbtrace.redraw_backlog(ours, selected, pick, np.random.default_rng(1))
    r3 = fbtrace.redraw_backlog(ours, selected, pick, np.random.default_rng(2))
    assert r1.n_flows == r3.n_flows == b.n_flows
    assert np.array_equal(r1.demands, r2.demands)
    assert not np.array_equal(r1.demands, r3.demands)
    assert sorted(np.count_nonzero(d) for d in r3.demands) == \
        sorted(np.count_nonzero(d) for d in b.demands)

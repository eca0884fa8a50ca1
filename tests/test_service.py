"""Fabric-manager service suite: incremental-vs-replay bit-exactness,
circuit-program round-trips, cache-hit correctness, and backpressure.

The load-bearing gate is ``engine.cross_check_incremental``: streaming an
arrival sequence through ``FabricState`` tick by tick must commit circuits
BIT-IDENTICAL (cores, establishment times, CCTs) to one ``run_fast_online``
replay of the whole stream — across random arrival patterns, tick
partitions, algorithms, and every incremental scheduling policy.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.core import (
    FabricState,
    FlatAssignState,
    assign_fast,
    extract_flows,
    order_coflows,
    run_fast,
    run_fast_online,
    sample_instance,
    sample_online_instance,
    synth_fb_trace,
)
from repro.core.coflow import Coflow, OnlineInstance
from repro.core.engine import cross_check_incremental
from repro.service import (
    AdmissionQueue,
    ArrivalRequest,
    BackpressureError,
    FabricConfig,
    FabricManager,
    compile_schedule,
    instance_key,
    merge_programs,
)

TRACE = synth_fb_trace(200, seed=2026)
RATES = (10.0, 20.0, 30.0)


def _stream(N=12, M=25, seed=0, span_factor=1.0, delta=8.0):
    off = sample_online_instance(TRACE, N=N, M=M, rates=RATES, delta=delta,
                                 span=0.0, seed=seed)
    mk = float(run_fast_online(off, "ours").ccts.max())
    return sample_online_instance(TRACE, N=N, M=M, rates=RATES, delta=delta,
                                  span=mk * span_factor, seed=seed)


# ---------------------------------------------------------------------------
# incremental engine vs full replay (the tentpole gate)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("algorithm", ["ours", "rho-assign", "rand-assign"])
def test_incremental_bit_exact_random_streams(seed, algorithm):
    oinst = _stream(seed=seed, span_factor=[0.5, 1.0, 2.0][seed % 3])
    cross_check_incremental(oinst, algorithm, seed=seed,
                            n_ticks=3 + seed * 2)


@pytest.mark.parametrize("scheduling", ["work-conserving"])
def test_incremental_bit_exact_all_schedulings(scheduling):
    """The service's one scheduling: the manager accepts it as stated, and
    the incremental state commits what the full replay schedules."""
    FabricManager(FabricConfig(rates=RATES, delta=8.0, N=12,
                               scheduling=scheduling))
    oinst = _stream(seed=5, span_factor=1.0)
    cross_check_incremental(oinst, "ours", n_ticks=6)


def test_incremental_simultaneous_release_single_tick():
    """All releases 0 in one tick reduces to the offline schedule."""
    oinst = _stream(seed=1, span_factor=0.0)
    cross_check_incremental(oinst, "ours", tick_times=[0.0])


def test_incremental_one_tick_per_coflow():
    """The finest admission granularity: every arrival is its own tick."""
    oinst = _stream(M=15, seed=2, span_factor=1.5)
    ticks = np.unique(oinst.releases)
    cross_check_incremental(oinst, "ours", tick_times=ticks)


def test_incremental_irregular_ticks():
    rng = np.random.default_rng(9)
    oinst = _stream(seed=3, span_factor=1.0)
    hi = float(oinst.releases.max())
    ticks = np.sort(rng.uniform(0, hi, 5))
    cross_check_incremental(oinst, "ours", tick_times=ticks)


def test_fabric_state_rejects_late_and_future_arrivals():
    c = Coflow(cid=0, demand=np.eye(4))
    st = FabricState(rates=np.array(RATES), delta=1.0, N=4)
    st.step([c], [3.0], 5.0)
    with pytest.raises(ValueError, match="late arrival"):
        st.step([c], [4.0], 10.0)
    with pytest.raises(ValueError, match="queue it"):
        st.step([c], [20.0], 10.0)
    with pytest.raises(ValueError, match="non-decreasing"):
        st.step((), (), 1.0)


def test_sunflow_is_benchmark_only_in_the_service():
    """ROADMAP resolution: the sunflow baselines pick the next coflow at
    core-free time — a decision later arrivals can overturn arbitrarily far
    in the future — so they cannot commit tick-by-tick. They are marked
    benchmark-only with a pinned error in both FabricState and
    FabricManager (replay entry points still serve them, next test)."""
    for algorithm in ("sunflow-core", "rand-sunflow"):
        with pytest.raises(ValueError, match="benchmark-only"):
            FabricState(rates=np.array(RATES), delta=1.0, N=4,
                        algorithm=algorithm)
    # the historical phrasing stays pinned too (docs/messages link to it)
    with pytest.raises(ValueError, match="full run_fast_online replay"):
        FabricState(rates=np.array(RATES), delta=1.0, N=4,
                    algorithm="sunflow-core")
    with pytest.raises(ValueError, match="sunflow"):
        FabricState(rates=np.array(RATES), delta=1.0, N=4,
                    algorithm="rand-sunflow")
    with pytest.raises(ValueError, match="work-conserving"):
        FabricManager(FabricConfig(rates=RATES, delta=1.0, N=4,
                                   scheduling="sunflow"))


def test_sunflow_replay_path_still_serves():
    """The full-replay entry points (the benchmark path) schedule the
    sunflow baselines end to end, online and offline, and the result passes
    the independent referee."""
    from repro.core import run_fast, validate

    oinst = _stream(M=10, seed=12, span_factor=1.0)
    s = run_fast_online(oinst, "sunflow-core")
    validate(s, releases=oinst.releases)
    s2 = run_fast(oinst.inst, "rand-sunflow", seed=3)
    validate(s2)
    # the service's ONE-SHOT plane is a full replay, so it serves sunflow
    # too (only the tick-committing streaming plane cannot)
    mgr = FabricManager(FabricConfig(rates=RATES, delta=8.0, N=12))
    program, _hit = mgr.schedule_instance(oinst.inst,
                                          algorithm="sunflow-core")
    program.validate()


def test_chunked_random_assignment_matches_one_shot():
    """Generator.choice consumes the bit stream identically chunked or not —
    the property the streaming rand-assign path rests on."""
    inst = sample_instance(TRACE, N=10, M=20, rates=RATES, delta=8.0, seed=4)
    pi = order_coflows(inst)
    flows = extract_flows(inst, pi)
    one = assign_fast(inst, pi, "random", seed=11, flows=flows)
    st = FlatAssignState("random", np.array(RATES), 8.0, 10, seed=11)
    fi, fj, sizes = flows[2], flows[3], flows[4]
    got, lo = [], 0
    for hi in (3, 10, 11, 25, fi.size):
        got.append(st.assign(fi[lo:hi], fj[lo:hi], sizes[lo:hi]))
        lo = hi
    assert np.array_equal(np.concatenate(got), one)


# ---------------------------------------------------------------------------
# service: manager, programs, cache, backpressure
# ---------------------------------------------------------------------------

def _drive(mgr: FabricManager, oinst: OnlineInstance, n_ticks: int):
    order = np.argsort(oinst.releases, kind="stable")
    rel = oinst.releases
    hi = float(rel.max())
    ticks = np.linspace(hi / n_ticks, hi, n_ticks) if hi > 0 else [0.0]
    nxt = 0
    for T in ticks:
        while nxt < order.size and rel[order[nxt]] <= T:
            m = int(order[nxt])
            mgr.submit(oinst.inst.coflows[m], float(rel[m]))
            nxt += 1
        mgr.tick(float(T))
    mgr.flush()
    return order


def test_manager_stream_matches_replay_and_programs_validate():
    oinst = _stream(seed=7, span_factor=1.0)
    mgr = FabricManager(FabricConfig(rates=RATES, delta=8.0, N=12,
                                     validate_every_tick=True))
    order = _drive(mgr, oinst, n_ticks=6)
    # per-tick programs validated inline; merged program validates too
    program = mgr.program()
    program.validate()
    # stream (admission order = release-sorted) vs full replay
    replay = OnlineInstance(
        inst=type(oinst.inst)(
            coflows=tuple(oinst.inst.coflows[int(m)] for m in order),
            rates=oinst.inst.rates, delta=oinst.inst.delta),
        releases=oinst.releases[order])
    fast = run_fast_online(replay, "ours")
    ref = {(int(fast.pi[f.coflow]), f.i, f.j): (f.core, f.t_establish)
           for f in fast.flows}
    got = {(int(g), int(i), int(j)): (int(c), float(t))
           for g, i, j, c, t in zip(program.cid, program.ingress,
                                    program.egress, program.core,
                                    program.t_establish)}
    assert got == ref
    assert np.array_equal(mgr.ccts(), fast.ccts)
    s = mgr.summary()
    assert s["coflows_finalized"] == oinst.inst.M
    assert s["decision_latency_p99_s"] >= s["decision_latency_p50_s"] >= 0


def test_summary_exports_tent_reuse_telemetry():
    """Delta-scheduling effectiveness is observable at the service boundary:
    summary() surfaces the engine's tent_reused/tent_recomputed counters and
    their fraction (0.0, not a division blow-up, on an idle manager)."""
    empty = FabricManager(FabricConfig(rates=RATES, delta=8.0, N=12))
    s0 = empty.summary()
    assert s0["tent_reused"] == 0 and s0["tent_recomputed"] == 0
    assert s0["tent_reuse_fraction"] == 0.0

    oinst = _stream(seed=8, span_factor=1.0)
    mgr = FabricManager(FabricConfig(rates=RATES, delta=8.0, N=12))
    _drive(mgr, oinst, n_ticks=6)
    s = mgr.summary()
    assert s["tent_reused"] == mgr.state.tent_reused
    assert s["tent_recomputed"] == mgr.state.tent_recomputed
    total = s["tent_reused"] + s["tent_recomputed"]
    assert total > 0
    assert s["tent_reuse_fraction"] == pytest.approx(s["tent_reused"] / total)
    assert 0.0 <= s["tent_reuse_fraction"] <= 1.0


def test_program_round_trip_through_validate():
    """A program rebuilt as a Schedule satisfies the independent referee,
    and a tampered program does not."""
    oinst = _stream(seed=8, span_factor=0.5)
    s = run_fast_online(oinst, "ours")
    program = compile_schedule(s)
    program.validate()
    sched = program.as_schedule()
    assert sorted(np.round(sched.ccts, 9)) == sorted(np.round(s.ccts, 9))
    # tamper: shift one segment to overlap its port neighbour
    bad = merge_programs([program, program], program.rates, program.delta,
                         program.N)
    with pytest.raises(AssertionError, match="port exclusivity"):
        bad.validate()


def test_program_events_time_ordered():
    oinst = _stream(M=10, seed=9, span_factor=1.0)
    program = compile_schedule(run_fast_online(oinst, "ours"))
    events = list(program.events())
    assert len(events) == 2 * program.n_segments
    times = [e.t for e in events]
    assert times == sorted(times)
    # establishment count == teardown count per core
    for k in range(program.K):
        kinds = [e.kind for e in events if e.core == k]
        assert kinds.count("establish") == kinds.count("teardown")


def test_cache_hit_returns_identical_program():
    inst = sample_instance(TRACE, N=10, M=15, rates=RATES, delta=8.0, seed=3)
    mgr = FabricManager(FabricConfig(rates=RATES, delta=8.0, N=10))
    p1, hit1 = mgr.schedule_instance(inst)
    p2, hit2 = mgr.schedule_instance(inst)
    assert (hit1, hit2) == (False, True)
    fresh = compile_schedule(run_fast(inst, "ours"))
    for attr in ("core", "ingress", "egress", "cid", "size", "t_establish",
                 "t_complete"):
        assert np.array_equal(getattr(p2, attr), getattr(fresh, attr))
    # different knobs / demands miss
    _p3, hit3 = mgr.schedule_instance(inst, algorithm="rho-assign")
    assert not hit3
    assert mgr.cache.hits == 1 and mgr.cache.misses == 2


def test_instance_key_sensitivity():
    inst = sample_instance(TRACE, N=8, M=6, rates=RATES, delta=8.0, seed=1)
    k0 = instance_key(inst)
    assert k0 == instance_key(inst)
    assert k0 != instance_key(inst, algorithm="rho-assign")
    assert k0 != instance_key(inst, releases=np.zeros(inst.M))
    bumped = type(inst)(
        coflows=tuple(inst.coflows[:-1]) + (
            Coflow(cid=inst.coflows[-1].cid,
                   demand=inst.coflows[-1].demand * 2.0,
                   weight=inst.coflows[-1].weight),),
        rates=inst.rates, delta=inst.delta)
    assert k0 != instance_key(bumped)


def test_cache_hit_relabels_cids():
    """A hit from a cid-relabeled twin submission carries the caller's ids
    (the key excludes labels by design), so downstream weight/cct joins by
    cid stay correct."""
    inst = sample_instance(TRACE, N=8, M=6, rates=RATES, delta=8.0, seed=2)
    twin = type(inst)(
        coflows=tuple(
            Coflow(cid=c.cid + 100, demand=c.demand, weight=c.weight)
            for c in inst.coflows),
        rates=inst.rates, delta=inst.delta)
    mgr = FabricManager(FabricConfig(rates=RATES, delta=8.0, N=8))
    p1, hit1 = mgr.schedule_instance(inst)
    p2, hit2 = mgr.schedule_instance(twin)
    assert (hit1, hit2) == (False, True)
    assert np.array_equal(p2.cid, p1.cid + 100)
    assert np.array_equal(p2.t_establish, p1.t_establish)
    # the service planner path (the original KeyError site) works on hits
    from repro.comm.planner import OCSFabric, plan_circuits_service
    fab = OCSFabric(rates=tuple(RATES), delta=8.0)
    _r1, m2 = plan_circuits_service(list(inst.coflows), fab,
                                    algorithms=("ours",))
    r2, _ = plan_circuits_service(list(twin.coflows), fab,
                                  algorithms=("ours",), manager=m2)
    assert r2["ours"].cached


def test_cache_hit_relabels_duplicate_cid_submissions():
    """Canonical (index-labeled) cache storage: even when the FIRST
    submission used duplicate cids, a later twin's hit gets ITS labels."""
    inst = sample_instance(TRACE, N=8, M=4, rates=RATES, delta=8.0, seed=5)
    dup = type(inst)(
        coflows=tuple(Coflow(cid=7, demand=c.demand, weight=c.weight)
                      for c in inst.coflows),
        rates=inst.rates, delta=inst.delta)
    mgr = FabricManager(FabricConfig(rates=RATES, delta=8.0, N=8))
    _p1, hit1 = mgr.schedule_instance(dup)
    p2, hit2 = mgr.schedule_instance(inst)
    assert (hit1, hit2) == (False, True)
    assert set(p2.cid.tolist()) <= {c.cid for c in inst.coflows}


def test_bad_submission_rejected_without_losing_the_batch():
    """A malformed request is rejected at submit; and if a tick's engine
    step ever fails, the drained batch is re-queued, not dropped."""
    mgr = FabricManager(FabricConfig(rates=RATES, delta=1.0, N=4))
    good = Coflow(cid=0, demand=np.eye(4))
    with pytest.raises(ValueError, match="fabric has N=4"):
        mgr.submit(Coflow(cid=1, demand=np.eye(3)), 1.0)
    mgr.submit(good, 1.0)
    # defense in depth: a failing engine step must not lose admitted work
    real_step = mgr.state.step
    mgr.state.step = lambda *a, **k: (_ for _ in ()).throw(RuntimeError("boom"))
    with pytest.raises(RuntimeError, match="boom"):
        mgr.tick(2.0)
    assert mgr.queue.depth == 1
    mgr.state.step = real_step
    mgr.tick(2.0)
    mgr.flush()
    assert mgr.summary()["coflows_finalized"] == 1


def test_repeated_tick_time_holds_late_requests():
    """A tick that repeats the committed time has an empty admission window;
    late requests must be held, not clamped into an inadmissible release."""
    c = Coflow(cid=0, demand=np.eye(4))
    mgr = FabricManager(FabricConfig(rates=RATES, delta=1.0, N=4))
    mgr.tick(10.0)
    mgr.submit(c, 5.0)
    rep = mgr.tick(10.0)  # window (10, 10] is empty — request stays queued
    assert rep.admitted == 0 and mgr.queue.depth == 1
    rep = mgr.tick(11.0)  # window reopens: clamped + admitted
    assert rep.admitted == 1 and mgr.queue.late == 1
    mgr.flush()
    assert mgr.summary()["coflows_finalized"] == 1


def test_planner_service_parity_with_zero_demand_coflow():
    """plan_circuits_service must report the same quantiles as plan_circuits
    even when a coflow has no traffic (its 0.0 CCT pads the distribution)."""
    from repro.comm.planner import OCSFabric, plan_circuits, plan_circuits_service
    rng = np.random.default_rng(3)
    cfs = [Coflow(cid=m, demand=rng.random((6, 6)) * (rng.random((6, 6)) < 0.4),
                  weight=1.0 + m) for m in range(5)]
    cfs.append(Coflow(cid=5, demand=np.zeros((6, 6)), weight=4.0))
    fab = OCSFabric(rates=(10.0, 20.0), delta=2.0)
    ref = plan_circuits(cfs, fab, algorithms=("ours",))["ours"]
    got = plan_circuits_service(cfs, fab, algorithms=("ours",))[0]["ours"]
    for k in ("total_cct", "weighted_cct", "makespan", "p95", "p99"):
        assert abs(getattr(ref, k) - getattr(got, k)) < 1e-9, k


def test_sample_online_instance_empty():
    oi = sample_online_instance(TRACE, N=6, M=0, rates=RATES, delta=8.0,
                                span=10.0, seed=0)
    assert oi.inst.M == 0 and oi.releases.shape == (0,)


def test_backpressure_and_late_clamp():
    q = AdmissionQueue(max_depth=2)
    c = Coflow(cid=0, demand=np.eye(3))
    q.push(ArrivalRequest(coflow=c, release=1.0, submitted_s=0.0))
    q.push(ArrivalRequest(coflow=c, release=9.0, submitted_s=0.0))
    with pytest.raises(BackpressureError):
        q.push(ArrivalRequest(coflow=c, release=2.0, submitted_s=0.0))
    assert q.rejected == 1
    # drain at t=5 with committed floor t=1: the release-1.0 request is late
    admitted = q.drain(5.0, 1.0)
    assert [r.release for r in admitted] == [float(np.nextafter(1.0, np.inf))]
    assert q.late == 1 and q.depth == 1  # release-9.0 request stays queued


def test_manager_backpressure_end_to_end():
    oinst = _stream(M=12, seed=6, span_factor=2.0)
    mgr = FabricManager(FabricConfig(rates=RATES, delta=8.0, N=12,
                                     max_queue_depth=3))
    order = np.argsort(oinst.releases, kind="stable")
    rejected = 0
    for m in order:
        try:
            mgr.submit(oinst.inst.coflows[int(m)],
                       float(oinst.releases[int(m)]))
        except BackpressureError:
            rejected += 1
    assert rejected == oinst.inst.M - 3
    assert mgr.summary()["rejected"] == rejected
    mgr.flush()
    assert mgr.summary()["coflows_finalized"] == 3


def test_zero_flow_coflow_finalizes_immediately():
    empty = Coflow(cid=0, demand=np.zeros((4, 4)))
    full = Coflow(cid=1, demand=np.eye(4))
    st = FabricState(rates=np.array(RATES), delta=1.0, N=4)
    out = st.step([empty, full], [0.5, 0.7], 1.0)
    fins = {f[0]: f[2] for f in out.finalized}
    assert fins.get(0) == 0.0
    st.finalize()
    assert st.ccts()[0] == 0.0 and st.ccts()[1] > 0.0

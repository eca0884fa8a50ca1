"""Fault differential harness: the two bit-exactness anchors of the fault
subsystem, plus the service-level fault plane.

The load-bearing gates (fuzzed over random online instances):

  (a) a ``FaultInjector`` with ZERO events is bit-identical to a plain
      ``FabricState`` tick by tick — the fault machinery may not perturb a
      single float of the healthy path;
  (b) a core failed at t=0 is bit-identical to scheduling on the
      (K-1)-core instance from scratch (commits mapped through the
      surviving-core indices) — degraded operation IS the smaller fabric,
      not an approximation of it.

Then the service plane: ``FabricManager.report_fault`` aborts in-flight
circuits with corrective teardowns, re-queues their demand, purges affected
cache entries, keeps the merged program of record valid, and the
``ElasticTrainer`` wiring shrinks mesh + circuit plane in one story.
"""
from __future__ import annotations

import types

import numpy as np
import pytest

from repro.core import (
    CoreDown,
    CoreUp,
    DeltaDrift,
    FabricState,
    FaultInjector,
    PortFlap,
    run_fast_online,
    sample_instance,
    sample_online_instance,
    synth_fb_trace,
)
from repro.core.coflow import Coflow
from repro.service import FabricConfig, FabricManager

TRACE = synth_fb_trace(200, seed=2026)
RATES = (10.0, 20.0, 30.0)
K = len(RATES)


def _stream(N=10, M=16, seed=0, span=300.0, delta=8.0):
    return sample_online_instance(TRACE, N=N, M=M, rates=RATES, delta=delta,
                                  span=span, seed=seed)


def _run_ticks(state: FabricState, oinst, ticks):
    """Drive a release-partitioned stream through ``state``; returns the
    per-tick commits (including the finalize tick)."""
    rel = oinst.releases
    out, prev = [], -np.inf
    for T in ticks:
        ids = np.nonzero((rel > prev) & (rel <= T))[0]
        out.append(state.step(
            [oinst.inst.coflows[int(m)] for m in ids], rel[ids], float(T)))
        prev = T
    out.append(state.finalize())
    return out


def _assert_commits_equal(got, ref, core_map=None):
    """Tick-by-tick bit-equality of two commit streams; ``core_map`` maps
    the reference run's (compacted) core ids to physical ids."""
    assert len(got) == len(ref)
    for ca, cb in zip(got, ref):
        assert ca.t_now == cb.t_now
        for f in ("gid", "cid", "fi", "fj", "size", "t_establish",
                  "t_complete"):
            assert np.array_equal(getattr(ca, f), getattr(cb, f)), f
        cores = cb.core if core_map is None else np.asarray(core_map)[cb.core]
        assert np.array_equal(ca.core, cores)
        assert ca.finalized == cb.finalized
        assert ca.n_pending == cb.n_pending


def _ticks_for(oinst, n_ticks):
    hi = float(oinst.releases.max()) if oinst.releases.size else 0.0
    return np.linspace(hi / n_ticks, hi, n_ticks) if hi > 0 else np.zeros(1)


# ---------------------------------------------------------------------------
# (a) zero-event injector == plain FabricState, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", range(8))
def test_zero_event_injector_bit_identical(seed):
    algorithm = ["ours", "rho-assign", "rand-assign"][seed % 3]
    oinst = _stream(seed=seed, span=[0.0, 200.0, 500.0][seed % 3])
    ticks = _ticks_for(oinst, 3 + seed % 4)
    plain = FabricState(rates=np.array(RATES), delta=8.0, N=10,
                        algorithm=algorithm, seed=seed)
    faulty = FabricState(rates=np.array(RATES), delta=8.0, N=10,
                         algorithm=algorithm, seed=seed,
                         faults=FaultInjector([]))
    _assert_commits_equal(_run_ticks(faulty, oinst, ticks),
                          _run_ticks(plain, oinst, ticks))
    assert np.array_equal(faulty.ccts(), plain.ccts())
    assert faulty.track_commits and not plain.track_commits


# ---------------------------------------------------------------------------
# (b) core down at t=0 == the (K-1)-core instance from scratch
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed,k_fail", [(s, s % K) for s in range(6)])
@pytest.mark.parametrize("algorithm", ["ours", "rand-assign"])
def test_core_down_at_zero_equals_k_minus_one(seed, k_fail, algorithm):
    oinst = _stream(seed=seed, span=250.0)
    ticks = _ticks_for(oinst, 4)
    faulted = FabricState(
        rates=np.array(RATES), delta=8.0, N=10, algorithm=algorithm,
        seed=seed, faults=FaultInjector([CoreDown(t=0.0, core=k_fail)]))
    up_idx = [k for k in range(K) if k != k_fail]
    reference = FabricState(rates=np.array(RATES)[up_idx], delta=8.0, N=10,
                            algorithm=algorithm, seed=seed)
    _assert_commits_equal(_run_ticks(faulted, oinst, ticks),
                          _run_ticks(reference, oinst, ticks),
                          core_map=up_idx)
    assert np.array_equal(faulted.ccts(), reference.ccts())


@pytest.mark.parametrize("scheduling", ["work-conserving"])
def test_core_down_at_zero_all_schedulings(scheduling):
    """One case: work-conserving is the one scheduling the incremental
    path runs."""
    oinst = _stream(seed=11, span=300.0)
    ticks = _ticks_for(oinst, 5)
    faulted = FabricState(
        rates=np.array(RATES), delta=8.0, N=10,
        faults=FaultInjector([CoreDown(t=0.0, core=1)]))
    reference = FabricState(rates=np.array(RATES)[[0, 2]], delta=8.0, N=10)
    _assert_commits_equal(_run_ticks(faulted, oinst, ticks),
                          _run_ticks(reference, oinst, ticks),
                          core_map=[0, 2])


@pytest.mark.slow
def test_fault_differential_fuzz_slow():
    """The long fuzz lane: ~30 more random instances across both anchors."""
    for seed in range(15):
        oinst = _stream(M=20, seed=100 + seed, span=50.0 * (seed % 5))
        ticks = _ticks_for(oinst, 2 + seed % 5)
        plain = FabricState(rates=np.array(RATES), delta=8.0, N=10)
        zero = FabricState(rates=np.array(RATES), delta=8.0, N=10,
                           faults=FaultInjector([]))
        _assert_commits_equal(_run_ticks(zero, oinst, ticks),
                              _run_ticks(plain, oinst, ticks))
        k_fail = seed % K
        up_idx = [k for k in range(K) if k != k_fail]
        down = FabricState(
            rates=np.array(RATES), delta=8.0, N=10,
            faults=FaultInjector([CoreDown(t=0.0, core=k_fail)]))
        ref = FabricState(rates=np.array(RATES)[up_idx], delta=8.0, N=10)
        _assert_commits_equal(_run_ticks(down, oinst, ticks),
                              _run_ticks(ref, oinst, ticks), core_map=up_idx)


# ---------------------------------------------------------------------------
# FabricState fault semantics (deterministic unit anchors)
# ---------------------------------------------------------------------------

def _big_coflow(n=4, size=100.0):
    D = np.zeros((n, n))
    for p in range(n - 1):
        D[p, p + 1] = size
    return Coflow(cid=0, demand=D)


def test_core_down_aborts_in_flight_and_requeues():
    """In-flight circuits on a failed core deliver nothing: full demand is
    re-queued after the fault, reassigned off the core, and the coflow's
    previously-final CCT is retracted then re-finalized."""
    st = FabricState(rates=np.array([10.0, 10.0, 10.0]), delta=1.0, N=4,
                     track_commits=True)
    out = st.step([_big_coflow()], [0.5], 1.0)
    assert out.n_flows == 3 and out.finalized  # committed, CCT "final"
    cct_before = st.ccts()[0]
    failed = int(out.core[0])
    app = st.apply_fault(CoreDown(t=2.0, core=failed))
    aborted_here = int((out.core == failed).sum())
    assert app.n_aborted == aborted_here == app.requeued
    assert app.unfinalized == (0,)
    out2 = st.finalize()
    assert not np.any(out2.core == failed)
    assert (out2.t_establish >= 2.0).all()
    assert float(out2.size.sum()) == aborted_here * 100.0  # re-served once
    assert st.ccts()[0] >= cct_before  # restart after the fault only delays
    assert st.n_pending_flows == 0


def test_completed_circuits_survive_core_down():
    st = FabricState(rates=np.array([10.0, 10.0]), delta=1.0, N=4,
                     track_commits=True)
    out = st.step([_big_coflow(size=10.0)], [0.0], 50.0)  # all done by t=12
    app = st.apply_fault(CoreDown(t=40.0, core=int(out.core[0])))
    assert app.n_aborted == 0 and app.unfinalized == ()
    assert st.ccts()[0] == out.t_complete.max()


def test_port_flap_aborts_overlaps_and_delays_rematch():
    st = FabricState(rates=np.array([10.0, 10.0]), delta=1.0, N=4,
                     track_commits=True)
    out = st.step([_big_coflow()], [0.5], 1.0)
    core0 = int(out.core[0])
    app = st.apply_fault(PortFlap(t=2.0, t_end=60.0, core=core0, port=0))
    assert app.n_aborted == 1  # only the (0 -> 1) flow touches port 0
    out2 = st.finalize()
    for x in range(out2.n_flows):
        if int(out2.core[x]) == core0 and (out2.fi[x] == 0 or out2.fj[x] == 0):
            assert out2.t_establish[x] >= 60.0
    assert st.n_pending_flows == 0


def test_core_up_restores_scheduling_on_the_core():
    st = FabricState(rates=np.array([10.0, 10.0]), delta=1.0, N=4,
                     faults=FaultInjector([CoreDown(t=0.0, core=1),
                                           CoreUp(t=100.0, core=1)]))
    st.step([_big_coflow(size=10.0)], [0.0], 50.0)
    assert not st.core_up[1]
    out = st.step([_big_coflow(size=10.0)], [120.0], 150.0)
    assert st.core_up[1]
    assert bool(np.any(out.core == 1))  # the fresh greedy uses it again


def test_delta_drift_prices_and_times_the_core():
    st = FabricState(rates=np.array([10.0, 10.0]), delta=1.0, N=4,
                     faults=FaultInjector([DeltaDrift(t=0.0, core=0,
                                                      delta=5.0)]))
    out = st.step([_big_coflow(size=10.0)], [0.0], 100.0)
    assert out.delta_f is not None
    gap = out.t_complete - out.t_establish - out.size / 10.0
    want = np.where(out.core == 0, 5.0, 1.0)
    assert np.allclose(gap, want)
    assert np.array_equal(out.delta_f, want)


def test_fault_error_cases():
    st = FabricState(rates=np.array(RATES), delta=1.0, N=4,
                     track_commits=True)
    with pytest.raises(ValueError, match="out of range"):
        st.apply_fault(CoreDown(t=0.0, core=7))
    with pytest.raises(ValueError, match="already up"):
        st.apply_fault(CoreUp(t=0.0, core=1))
    st.apply_fault(CoreDown(t=0.0, core=0))
    with pytest.raises(ValueError, match="already down"):
        st.apply_fault(CoreDown(t=0.0, core=0))
    st.apply_fault(CoreDown(t=0.0, core=1))
    with pytest.raises(RuntimeError, match="fabric lost"):
        st.apply_fault(CoreDown(t=0.0, core=2))
    assert st.core_up[2]  # the refused failure did not stick
    with pytest.raises(TypeError, match="unknown fault event"):
        st.apply_fault("core-down")
    with pytest.raises(ValueError, match="non-empty"):
        PortFlap(t=5.0, t_end=5.0, core=0, port=0)
    untracked = FabricState(rates=np.array(RATES), delta=1.0, N=4)
    with pytest.raises(RuntimeError, match="track_commits"):
        untracked.apply_fault(CoreDown(t=0.0, core=0))


# ---------------------------------------------------------------------------
# service plane: report_fault, program of record, degraded one-shot
# ---------------------------------------------------------------------------

def _drive(mgr, oinst, ticks, fault_after=None, fault=None):
    order = np.argsort(oinst.releases, kind="stable")
    rel = oinst.releases
    nxt = 0
    report = None
    for i, T in enumerate(ticks):
        while nxt < order.size and rel[order[nxt]] <= T:
            m = int(order[nxt])
            mgr.submit(oinst.inst.coflows[m], float(rel[m]))
            nxt += 1
        mgr.tick(float(T))
        if fault_after == i:
            report = mgr.report_fault(fault)
    mgr.flush()
    return report


def test_manager_report_fault_end_to_end():
    """Mid-stream core failure through the manager: corrective teardowns
    cover exactly the aborted circuits, every coflow still finalizes
    exactly once in the counters, and the merged program of record
    validates with the aborted segments excluded."""
    oinst = _stream(M=24, seed=4, span=400.0)
    ticks = _ticks_for(oinst, 6)
    mgr = FabricManager(FabricConfig(rates=RATES, delta=8.0, N=10,
                                     validate_every_tick=True))
    fault = CoreDown(t=float(ticks[2]) + 0.5, core=2)
    rep = _drive(mgr, oinst, ticks, fault_after=2, fault=fault)
    assert rep is not None and rep.aborted == rep.requeued == len(rep.teardowns)
    for ev in rep.teardowns:
        assert ev.kind == "teardown" and ev.core == 2 and ev.t == fault.t
    s = mgr.summary()
    assert s["coflows_finalized"] == oinst.inst.M
    assert s["cores_up"] == 2 and s["faults_applied"] == 1
    # one decision-latency sample per coflow: a fault-retracted coflow
    # re-finalizing must not inject a second (bogus 0.0) sample
    assert len(mgr.latencies_s) == oinst.inst.M
    program = mgr.program()
    program.validate()
    # nothing in the program of record establishes on core 2 after the fault
    late = program.t_establish > fault.t
    assert not np.any(program.core[late] == 2)
    # bytes are served exactly once
    sent = np.zeros((oinst.inst.M, 10, 10))
    # program cid is the admission gid == release-sorted stream position
    order = np.argsort(oinst.releases, kind="stable")
    np.add.at(sent, (program.cid, program.ingress, program.egress),
              program.size)
    want = np.stack([oinst.inst.coflows[int(m)].demand for m in order])
    assert np.allclose(sent, want)


def test_manager_injected_faults_reported_per_tick():
    oinst = _stream(M=18, seed=9, span=300.0)
    ticks = _ticks_for(oinst, 5)
    inj = FaultInjector([CoreDown(t=float(ticks[1]) + 1.0, core=1)])
    mgr = FabricManager(FabricConfig(rates=RATES, delta=8.0, N=10,
                                     validate_every_tick=True, faults=inj))
    _drive(mgr, oinst, ticks)
    assert mgr.summary()["faults_applied"] == 1
    assert len(mgr.fault_reports) == 1  # tick-applied churn is registered
    assert any(r.aborted == len(r.teardowns) for r in mgr.fault_reports)
    assert sum(r.aborted for r in mgr.reports) == mgr.fault_reports[0].aborted
    mgr.program().validate()
    assert mgr.summary()["coflows_finalized"] == oinst.inst.M


def test_degraded_one_shot_masks_core_and_fingerprints_cache():
    inst = sample_instance(TRACE, N=8, M=10, rates=RATES, delta=8.0, seed=3)
    mgr = FabricManager(FabricConfig(rates=RATES, delta=8.0, N=8))
    p_healthy, _ = mgr.schedule_instance(inst)
    assert 2 in set(p_healthy.core.tolist())
    rep = mgr.report_fault(CoreDown(t=0.0, core=2))
    assert rep.cache_purged == 1  # the healthy program used core 2
    p_deg, hit = mgr.schedule_instance(inst)
    assert not hit and 2 not in set(p_deg.core.tolist())
    assert np.array_equal(p_deg.rates, np.asarray(RATES))  # physical labels
    p_deg.validate()
    _p, hit2 = mgr.schedule_instance(inst)
    assert hit2  # degraded key is stable
    mgr.report_fault(CoreUp(t=0.0, core=2))
    p_back, hit3 = mgr.schedule_instance(inst)
    assert not hit3  # healthy key was purged, not masked away
    assert np.array_equal(p_back.core, p_healthy.core)


def test_degraded_planner_avoids_failed_core():
    from repro.comm.planner import OCSFabric, plan_circuits_service
    rng = np.random.default_rng(5)
    cfs = [Coflow(cid=m, demand=rng.random((6, 6)) * (rng.random((6, 6)) < 0.4))
           for m in range(5)]
    fab = OCSFabric(rates=(10.0, 20.0, 30.0), delta=2.0)
    reports, mgr = plan_circuits_service(cfs, fab, algorithms=("ours",))
    assert not reports["ours"].degraded
    mgr.report_fault(CoreDown(t=0.0, core=0))
    reports2, _ = plan_circuits_service(cfs, fab, algorithms=("ours",),
                                        manager=mgr)
    r = reports2["ours"]
    assert r.degraded and not r.cached
    assert 0 not in set(r.program.core.tolist())


def test_elastic_trainer_shrinks_mesh_and_circuit_plane_together():
    """DeviceLoss -> ElasticTrainer.shrink() -> fabric CoreDown, one story;
    grow() brings the core back."""
    from repro.distributed.fault import ElasticTrainer

    mgr = FabricManager(FabricConfig(rates=RATES, delta=8.0, N=6))
    mgr.submit(Coflow(cid=0, demand=np.eye(6) * 50.0), 1.0)
    mgr.tick(2.0)  # commit some circuits so the shrink has work to abort
    build = lambda mesh: (lambda s, b: (s, {}), lambda: {}, lambda s: {})
    meshes = [types.SimpleNamespace(shape={"data": 8}),
              types.SimpleNamespace(shape={"data": 4})]
    tr = ElasticTrainer(build, meshes, "/tmp/fault-ckpt-test",
                        fabric=mgr, mesh_cores=[(0, 1, 2), (0, 1)])
    tr.shrink()
    assert not mgr.state.core_up[2]
    assert any(e["event"] == "fabric-core-down" and e["core"] == 2
               for e in tr.events)
    tr.grow()
    assert bool(mgr.state.core_up.all())
    assert any(e["event"] == "fabric-core-up" for e in tr.events)
    mgr.flush()
    mgr.program().validate()
    with pytest.raises(ValueError, match="go together"):
        ElasticTrainer(build, meshes, "/tmp/fault-ckpt-test", fabric=mgr)
    with pytest.raises(ValueError, match="every mesh"):
        ElasticTrainer(build, meshes, "/tmp/fault-ckpt-test", fabric=mgr,
                       mesh_cores=[(0, 1, 2)])
    # a non-nested fallback chain would report a never-downed core "up"
    # mid-recovery; reject it up front
    with pytest.raises(ValueError, match="nested fallback chain"):
        ElasticTrainer(build, meshes, "/tmp/fault-ckpt-test", fabric=mgr,
                       mesh_cores=[(0, 1, 2), (1, 2, 3)])

"""The one-shot request's spans, in memory and in a JAX profiler trace.

Three contracts:

1. **Profiler bridge** — while a profiler session is active, every span of
   the one-shot taxonomy (``repro.obs.trace``) is also a ``fabric/<span>``
   annotation carrying its attributes, nested as the request nests; with no
   session ``NULL_TRACER.span()`` is still the shared no-op span.
2. **Bit-identical** — programs do not change with the profiler on or off,
   nor with a recording ``Tracer`` or ``NULL_TRACER``.
3. **Honest counters** — the event loop's ``events`` (heap pops) and
   ``candidates`` equal a plain recount from the times it produced.
"""
from __future__ import annotations

import glob
import os

import jax
import numpy as np
import pytest

from repro.core import sample_instance, synth_fb_trace
from repro.core.coflow import Instance, OnlineInstance
from repro.core.engine import (FlowTable, LoopCounts, _event_loop,
                               _times_for_table, build_flow_table)
from repro.core.ordering import order_coflows
from repro.obs import NULL_TRACER, Tracer, current_tracer, set_tracer
from repro.obs.trace import NULL_SPAN
from repro.service import FabricConfig, FabricManager

TRACE = synth_fb_trace(200, seed=2026)
RATES = (10.0, 20.0, 30.0)

#: span -> (parent span, attributes it carries), as in the taxonomy
TAXONOMY = {
    "oneshot": (None, {"coflows", "flows", "hit", "compiles",
                       "materialized"}),
    "oneshot/key": ("oneshot", set()),
    "oneshot/order": ("oneshot", set()),
    "oneshot/extract": ("oneshot", {"flows"}),
    "oneshot/assign": ("oneshot", {"flows", "impl"}),
    "oneshot/assign/put": ("oneshot/assign", set()),
    "oneshot/assign/launch": ("oneshot/assign", {"padded_flows"}),
    "oneshot/assign/fetch": ("oneshot/assign", set()),
    "oneshot/event_loop": ("oneshot", {"events", "candidates"}),
    "oneshot/schedule": ("oneshot", set()),
    "oneshot/emit": ("oneshot", {"segments"}),
    "oneshot/cache": ("oneshot", set()),
}


def _instance(M=8, seed=1):
    return sample_instance(TRACE, N=10, M=M, rates=RATES, delta=8.0,
                           seed=seed)


def _manager(tracer=None):
    return FabricManager(FabricConfig(rates=RATES, delta=8.0, N=10),
                         tracer=tracer)


def _program_tuple(p):
    return (p.cid.tolist(), p.ingress.tolist(), p.egress.tolist(),
            p.core.tolist(), p.t_establish.tolist(), p.t_complete.tolist())


def _profiled(tmp_path, fn):
    """Run ``fn`` under a profiler session; its result and the
    ``fabric/`` host events as ``(name, start, end, stats)``."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    events = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("fabric/"):
                    events.append((ev.name[len("fabric/"):],
                                   int(ev.start_ns),
                                   int(ev.start_ns + ev.duration_ns),
                                   dict(ev.stats)))
    return out, events


# ---------------------------------------------------------------------------
# the profiler bridge
# ---------------------------------------------------------------------------

def test_pallas_request_under_profiler_has_every_span_once(tmp_path):
    tr = Tracer()
    mgr = _manager(tr)
    inst = _instance()
    (program, hit), events = _profiled(
        tmp_path, lambda: mgr.schedule_instance(inst, backend="pallas"))
    assert not hit
    names = [e[0] for e in events]
    assert sorted(names) == sorted(TAXONOMY)
    by = {e[0]: e for e in events}
    for name, (parent, attrs) in TAXONOMY.items():
        _, s, e, stats = by[name]
        assert set(stats) == attrs, name
        if parent is not None:
            _, ps, pe, _ = by[parent]
            assert ps <= s and e <= pe, (name, parent)
    # the same spans in memory, nested by parent id, with the same values
    recs = {r["name"]: r for r in tr.records if r["kind"] == "span"}
    assert set(recs) == set(TAXONOMY)
    sid = {r["sid"]: r["name"] for r in recs.values()}
    for name, (parent, attrs) in TAXONOMY.items():
        assert sid.get(recs[name]["parent"]) == parent, name
        assert set(recs[name]["attrs"]) == attrs
        assert {k: by[name][3][k] for k in attrs} == {
            k: int(v) if isinstance(v, bool) else v
            for k, v in recs[name]["attrs"].items()}
    root = recs["oneshot"]["attrs"]
    assert root["coflows"] == inst.M and root["hit"] is False
    assert root["flows"] == program.n_segments
    assert recs["oneshot/assign"]["attrs"]["impl"] == "pallas"
    assert recs["oneshot/extract"]["attrs"]["flows"] == program.n_segments
    assert recs["oneshot/assign/launch"]["attrs"]["padded_flows"] >= \
        program.n_segments
    assert recs["oneshot/emit"]["attrs"]["segments"] == program.n_segments
    assert recs["oneshot/event_loop"]["attrs"]["events"] > 0


def test_null_tracer_spans_reach_the_profiler_alone(tmp_path):
    mgr = _manager()
    assert mgr._tracer is NULL_TRACER
    (_, _), events = _profiled(
        tmp_path, lambda: mgr.schedule_instance(_instance()))
    names = {e[0] for e in events}
    assert names == set(TAXONOMY) - {"oneshot/assign/put",
                                     "oneshot/assign/launch",
                                     "oneshot/assign/fetch"}
    by = {e[0]: e[3] for e in events}
    assert by["oneshot/assign"]["impl"] == "numpy"
    assert by["oneshot"]["hit"] == 0
    assert NULL_TRACER.records == []


def test_hit_request_spans_and_cache_counters(tmp_path):
    tr = Tracer()
    mgr = _manager(tr)
    inst = _instance()
    mgr.schedule_instance(inst)
    (_, hit), events = _profiled(tmp_path,
                                 lambda: mgr.schedule_instance(inst))
    assert hit
    assert sorted(e[0] for e in events) == [
        "oneshot", "oneshot/cache", "oneshot/key"]
    assert {e[0]: e[3] for e in events}["oneshot"]["hit"] == 1
    assert mgr.summary()["cache_hits"] == 1


def test_null_span_without_a_profiler_session(tmp_path):
    assert not jax.profiler.TraceAnnotation.is_enabled()
    assert NULL_TRACER.span("oneshot") is NULL_SPAN
    jax.profiler.start_trace(str(tmp_path))
    try:
        sp = NULL_TRACER.span("oneshot")
        assert sp is not NULL_SPAN and sp.live
        with sp:
            sp.set(flows=3)
    finally:
        jax.profiler.stop_trace()
    assert NULL_TRACER.span("oneshot") is NULL_SPAN
    assert not NULL_TRACER.span("x").live


def test_tracer_is_restored_after_a_request():
    tr = Tracer()
    prev = set_tracer(None)
    try:
        _manager(tr).schedule_instance(_instance())
        assert current_tracer() is NULL_TRACER
    finally:
        set_tracer(prev)


# ---------------------------------------------------------------------------
# bit-identical with the profiler and the tracer on or off
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("backend", ["numpy", "pallas"])
def test_programs_bit_identical_profiler_and_tracer(tmp_path, backend):
    inst = _instance(M=10, seed=3)
    oinst = OnlineInstance(inst=inst, releases=np.linspace(0, 40, inst.M))
    got = []
    for profiled in (False, True):
        for tracer in (None, Tracer()):
            def run():
                mgr = _manager(tracer)
                return [_program_tuple(mgr.schedule_instance(
                    x, backend=backend)[0]) for x in (inst, oinst)]
            if profiled:
                out, events = _profiled(tmp_path / f"{tracer is None}", run)
                assert events
            else:
                out = run()
            got.append(out)
    assert all(g == got[0] for g in got[1:])


# ---------------------------------------------------------------------------
# events and candidates against a plain recount
# ---------------------------------------------------------------------------

def _recount(rin, rout, core, srv, delta, t_est, release=None, guard=False):
    """Heap pops and candidates of one from-scratch loop (t0 = 0), from the
    times it produced: the loop pops every heap entry before its last start
    time T, and one entry equal to T. At each distinct popped time t, under
    ``guard`` it counts the pending flows of the cores active then; else it
    scans the pending flows (started at or after t) of each resource freed
    then, in index order, up to the one that starts on that resource at t,
    or all of them if none does, and every flow released then."""
    tc = (t_est + delta) + srv
    entries = tc.tolist()
    if release is not None:
        entries += np.unique(release).tolist()
    last = float(t_est.max())
    if last == 0.0:
        return 0, 0
    entries = np.asarray(entries)
    events = int((entries < last).sum()) + 1
    visited = np.unique(entries[(entries > 0) & (entries <= last)])
    cand = 0
    for t in visited:
        ending = tc == t
        rel_now = (np.zeros(rin.size, bool) if release is None
                   else release == t)
        pending = t_est >= t
        if guard:
            act = set(core[ending].tolist()) | set(
                core[pending & rel_now].tolist())
            cand += int((pending & np.isin(core, list(act))).sum())
            continue
        for res in (rin, rout):
            for r in np.unique(res[ending]).tolist():
                scanned = np.flatnonzero(pending & (res == r))
                now = scanned[t_est[scanned] == t]
                cand += (int(np.searchsorted(scanned, now[0])) + 1
                         if now.size else scanned.size)
        cand += int(rel_now.sum())
    return events, cand


def _random_loop_input(seed, F=40, K=2, N=4, online=False):
    rng = np.random.default_rng(seed)
    core = rng.integers(0, K, F)
    fi, fj = rng.integers(0, N, F), rng.integers(0, N, F)
    rates = np.array([10.0, 25.0])
    srv = rng.uniform(1.0, 50.0, F) / rates[core]
    release = np.round(rng.uniform(0, 60, F), 0) if online else None
    return core * N + fi, core * N + fj, srv, core, K * N, N, release


@pytest.mark.parametrize("guard", [False, True])
@pytest.mark.parametrize("online", [False, True])
@pytest.mark.parametrize("seed", range(6))
def test_loop_counts_equal_a_plain_recount(seed, online, guard):
    rin, rout, srv, core, n_res, n_ports, release = _random_loop_input(
        seed, online=online)
    counts = LoopCounts()
    t_est = _event_loop(rin, rout, srv, core, 8.0, n_res, n_ports,
                        guard=guard, release=release, counts=counts)
    plain = _event_loop(rin, rout, srv, core, 8.0, n_res, n_ports,
                        guard=guard, release=release)
    assert np.array_equal(t_est, plain)  # counting changes no time
    assert (counts.events, counts.candidates) == _recount(
        rin, rout, core, srv, 8.0, t_est, release=release, guard=guard)


def test_serial_flows_pop_one_event_a_flow():
    """Flows sharing one ingress port run one after another: the times are
    distinct and the loop pops F - 1 events. When flow f ends, the scan of
    the shared ingress port examines one flow, f + 1, which starts; that
    of f's egress port examines its later flows, none of which can start
    once f + 1 holds the ingress port."""
    F = 12
    rin = np.zeros(F, np.int64)
    rout = np.arange(F, dtype=np.int64) % 4
    srv = np.linspace(1.0, 3.0, F)
    counts = LoopCounts()
    t_est = _event_loop(rin, rout, srv, np.zeros(F, np.int64), 8.0, 4, 4,
                        counts=counts)
    assert np.all(np.diff(t_est) > 0)
    assert counts.events == F - 1
    later_on_egress = sum(len(range(f + 4, F, 4)) for f in range(F - 1))
    assert counts.candidates == (F - 1) + later_on_egress


@pytest.mark.parametrize("online", [False, True])
@pytest.mark.parametrize("seed", range(4))
def test_started_flow_is_never_examined_again(monkeypatch, seed, online):
    """Every flow a resource scan examines at an event t is pending then
    (starts at t or later), and no event examines one list entry twice:
    a flow leaves its lists when it starts."""
    from repro.core import engine

    seen = []
    scan = engine._scan_pending

    def recording(others, flows, free, t, q, rel):
        n, hit = scan(others, flows, free, t, q, rel)
        seen.extend((t, id(flows), f) for f in flows[q:q + n])
        return n, hit

    monkeypatch.setattr(engine, "_scan_pending", recording)
    rin, rout, srv, core, n_res, n_ports, release = _random_loop_input(
        seed, F=120, N=3, online=online)
    counts = LoopCounts()
    t_est = _event_loop(rin, rout, srv, core, 8.0, n_res, n_ports,
                        release=release, counts=counts)
    assert seen and len(seen) <= counts.candidates
    assert all(t_est[f] >= t for t, _lst, f in seen)
    assert len(set(seen)) == len(seen)


def test_one_shot_event_loop_span_carries_the_counts():
    inst = _instance(M=10, seed=2)
    pi = order_coflows(inst)
    table = build_flow_table(inst, pi)
    counts = LoopCounts()
    K, N = inst.K, inst.N
    rin, rout = table.core * N + table.fi, table.core * N + table.fj
    srv = table.size / inst.rates[table.core]
    _event_loop(rin, rout, srv, table.core, inst.delta, K * N, N,
                counts=counts)
    tr = Tracer()
    prev = set_tracer(tr)
    try:
        t_est, _ = _times_for_table(inst, pi, table)
    finally:
        set_tracer(prev)
    span, = [r for r in tr.records if r["name"] == "oneshot/event_loop"]
    assert span["attrs"] == {"events": counts.events,
                             "candidates": counts.candidates}
    assert (counts.events, counts.candidates) == _recount(
        rin, rout, table.core, srv, inst.delta, t_est)


def test_streaming_event_loop_span_carries_the_counts():
    from repro.core import sample_online_instance

    oinst = sample_online_instance(TRACE, N=10, M=12, rates=RATES,
                                   delta=8.0, span=200.0, seed=7)
    tr = Tracer()
    mgr = _manager(tr)
    for m in np.argsort(oinst.releases, kind="stable"):
        mgr.submit(oinst.inst.coflows[int(m)], float(oinst.releases[m]))
    mgr.flush()
    loops = [r["attrs"] for r in tr.records if r["name"] == "tick/event_loop"]
    assert loops and all({"rows", "events", "candidates"} <= set(a)
                         for a in loops)
    assert sum(a["events"] for a in loops) > 0


def test_reserving_and_sunflow_spans():
    inst = _instance(M=6, seed=4)
    pi = order_coflows(inst)
    table = build_flow_table(inst, pi)
    tr = Tracer()
    prev = set_tracer(tr)
    try:
        _times_for_table(inst, pi, table, "reserving")
        _times_for_table(inst, pi, table, "sunflow")
    finally:
        set_tracer(prev)
    res, sun = [r["attrs"] for r in tr.records
                if r["name"] == "oneshot/event_loop"]
    assert res == {}
    assert sun["events"] > 0 and sun["candidates"] > 0


def test_empty_instance_counts_nothing():
    counts = LoopCounts()
    e = np.zeros(0, np.int64)
    _event_loop(e, e, np.zeros(0), e, 8.0, 4, 4, counts=counts)
    assert (counts.events, counts.candidates) == (0, 0)
    table = FlowTable(pos=e, cid=e, fi=e, fj=e, core=e, size=np.zeros(0))
    inst = Instance(coflows=[], rates=np.array(RATES), delta=8.0)
    _times_for_table(inst, e, table)

"""Observability suite: tracer/metrics semantics and the two contracts.

The load-bearing gates:

1. **Free when disabled** — the default tracer is the shared
   ``NULL_TRACER`` whose ``span()`` returns one no-op singleton, so the
   disabled hot path allocates nothing and ``records`` stays empty.
2. **Bit-identical on or off** — tracing only observes. Driving the same
   arrival stream through two managers, one traced and one not, must
   commit identical CCTs and circuit programs — offline, online, and
   with a mid-stream fault injected.

Plus: JSONL/Chrome-trace schema validity of every span the fabric emits,
nesting well-formedness under ``BackpressureError`` and faults, the
``summary()`` latency-window coverage keys, and the ``python -m
repro.obs`` CLI contract (summarize / validate / diff / diff-bench /
export-chrome).
"""
from __future__ import annotations

import json
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.core import CoreDown, sample_online_instance, synth_fb_trace
from repro.core.coflow import Coflow
from repro.obs import (
    NULL_TRACER,
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    NullTracer,
    Tracer,
    current_tracer,
    set_tracer,
)
from repro.obs.cli import (
    check_floors,
    diff_bench,
    diff_phases,
    load_trace,
    main as obs_main,
    phase_stats,
    summarize,
    validate_records,
)
from repro.obs.trace import NULL_SPAN
from repro.service import BackpressureError, FabricConfig, FabricManager

REPO = Path(__file__).resolve().parent.parent
TRACE = synth_fb_trace(200, seed=2026)
RATES = (10.0, 20.0, 30.0)

#: every span name the instrumented fabric emits on a healthy stream
FABRIC_PHASES = {"tick", "tick/admit", "tick/assign", "tick/splice",
                 "tick/event_loop", "tick/program_emit"}


def _stream(N=10, M=16, seed=0, span=300.0, delta=8.0):
    return sample_online_instance(TRACE, N=N, M=M, rates=RATES, delta=delta,
                                  span=span, seed=seed)


def _drive(mgr, oinst, n_ticks=6, fault_after=None, fault=None):
    order = np.argsort(oinst.releases, kind="stable")
    rel = oinst.releases
    hi = float(rel.max())
    ticks = np.linspace(hi / n_ticks, hi, n_ticks) if hi > 0 else [0.0]
    nxt = 0
    for i, T in enumerate(ticks):
        while nxt < order.size and rel[order[nxt]] <= T:
            m = int(order[nxt])
            mgr.submit(oinst.inst.coflows[m], float(rel[m]))
            nxt += 1
        mgr.tick(float(T))
        if fault_after == i:
            mgr.report_fault(fault)
    mgr.flush()


def _program_tuple(mgr):
    p = mgr.program()
    return (p.cid.tolist(), p.ingress.tolist(), p.egress.tolist(),
            p.core.tolist(), p.t_establish.tolist(), p.t_complete.tolist())


# ---------------------------------------------------------------------------
# tracer semantics
# ---------------------------------------------------------------------------

def test_tracer_nesting_and_record_shape():
    tr = Tracer()
    with tr.span("tick") as outer:
        outer.set(tick=1)
        with tr.span("tick/admit") as inner:
            assert inner.depth == 1 and inner.parent == outer.sid
        tr.event("cache/miss", key="abc")
    assert tr.open_spans == 0
    kinds = [(r["kind"], r["name"], r["depth"]) for r in tr.records]
    # spans record at close: inner before outer; event carries its depth
    assert kinds == [("span", "tick/admit", 1), ("event", "cache/miss", 1),
                     ("span", "tick", 0)]
    root = tr.records[-1]
    assert root["parent"] is None and root["attrs"] == {"tick": 1}
    assert root["dur"] >= 0
    assert validate_records(tr.records) == []


def test_span_closes_and_flags_error_on_exception():
    tr = Tracer()
    with pytest.raises(RuntimeError):
        with tr.span("tick"):
            with tr.span("tick/assign"):
                raise RuntimeError("boom")
    assert tr.open_spans == 0
    assert [r["name"] for r in tr.records] == ["tick/assign", "tick"]
    assert all(r.get("error") is True for r in tr.records)
    assert validate_records(tr.records) == []


def test_null_tracer_is_the_shared_noop_singleton():
    assert isinstance(NULL_TRACER, NullTracer)
    sp = NULL_TRACER.span("tick")
    assert sp is NULL_SPAN and sp is NULL_TRACER.span("other")
    assert sp.live is False and sp.set(x=1) is sp
    with sp:
        pass
    NULL_TRACER.event("cache/hit", key="k")
    NULL_TRACER.flush()
    assert NULL_TRACER.records == [] and NULL_TRACER.open_spans == 0


def test_set_tracer_round_trip():
    tr = Tracer()
    assert current_tracer() is NULL_TRACER
    prev = set_tracer(tr)
    try:
        assert prev is NULL_TRACER and current_tracer() is tr
        # a manager built under an installed tracer picks it up
        mgr = FabricManager(FabricConfig(rates=RATES, delta=8.0, N=4))
        mgr.tick(1.0)
        assert any(r["name"] == "tick" for r in tr.records)
    finally:
        assert set_tracer(None) is tr
    assert current_tracer() is NULL_TRACER


def test_jsonl_sink_and_chrome_export(tmp_path):
    sink = tmp_path / "trace.jsonl"
    with Tracer(sink) as tr:
        with tr.span("tick") as sp:
            sp.set(bad=float("inf"), arr=np.float64(2.5), obj=object())
            tr.event("cache/purge", count=3)
    records = load_trace(sink)
    assert validate_records(records) == []
    span = next(r for r in records if r["kind"] == "span")
    # non-finite and non-scalar attrs are coerced, never break the JSON
    assert span["attrs"]["bad"] == "inf" and span["attrs"]["arr"] == 2.5
    assert isinstance(span["attrs"]["obj"], str)
    doc = tr.to_chrome_trace()
    phases = {e["ph"] for e in doc["traceEvents"]}
    assert phases == {"X", "i"} and doc["displayTimeUnit"] == "ms"
    x = next(e for e in doc["traceEvents"] if e["ph"] == "X")
    assert x["dur"] >= 0 and x["name"] == "tick"


# ---------------------------------------------------------------------------
# metrics semantics
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_semantics():
    c = Counter("service.finalized")
    c.inc(5)
    c.inc(-2)  # fault recovery un-finalizes
    assert c.value == 3
    g = Gauge("queue.depth")
    g.set(7)
    assert g.value == 7.0

    h = Histogram("lat", window=4)
    for v in [1.0, 2.0, 3.0]:
        h.observe(v)
    assert h.coverage == 1.0 and h.n_retained == h.n_observed == 3
    for v in [4.0, 5.0, 6.0]:
        h.observe(v)
    # window keeps the newest 4 of 6; accounting stays exact
    assert h.n_observed == 6 and h.n_retained == 4
    assert h.coverage == pytest.approx(4 / 6)
    assert h.total == pytest.approx(21.0)
    assert h.quantile(0.0) == 3.0 and h.quantile(1.0) == 6.0
    empty = Histogram("e")
    assert empty.coverage == 1.0 and empty.quantile(0.5) == 0.0
    assert empty.mean() == 0.0


def test_registry_get_or_create_and_snapshot():
    reg = MetricsRegistry()
    assert reg.counter("a.b") is reg.counter("a.b")
    assert reg.histogram("h") is reg.histogram("h")
    reg.counter("a.b").inc(2)
    reg.gauge("g").set(1.5)
    reg.histogram("h").observe(3.0)
    snap = reg.snapshot()
    assert snap["a.b"] == 2 and snap["g"] == 1.5
    assert snap["h.p50"] == 3.0 and snap["h.n_observed"] == 1
    assert snap["h.coverage"] == 1.0


# ---------------------------------------------------------------------------
# the differential gate: tracing on vs off is bit-identical
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", [0, 3])
def test_stream_bit_identical_with_tracing(seed):
    oinst = _stream(seed=seed)
    cfg = FabricConfig(rates=RATES, delta=8.0, N=10)
    off = FabricManager(cfg)
    tr = Tracer()
    on = FabricManager(cfg, tracer=tr)
    _drive(off, oinst)
    _drive(on, oinst)
    assert np.array_equal(off.ccts(), on.ccts())
    assert _program_tuple(off) == _program_tuple(on)
    # the traced run actually traced: every fabric phase present + valid
    assert off._tracer is NULL_TRACER and off._tracer.records == []
    assert validate_records(tr.records) == []
    assert tr.open_spans == 0
    assert FABRIC_PHASES <= set(phase_stats(tr.records))


def test_cache_traffic_emits_events_and_counters():
    oinst = _stream(M=8, seed=5)
    tr = Tracer()
    mgr = FabricManager(FabricConfig(rates=RATES, delta=8.0, N=10),
                        tracer=tr)
    _, hit0 = mgr.schedule_instance(oinst)
    _, hit1 = mgr.schedule_instance(oinst)
    assert (hit0, hit1) == (False, True)
    requests = [r["attrs"] for r in tr.records if r["name"] == "oneshot"]
    assert [a["hit"] for a in requests] == [False, True]
    assert not [r for r in tr.records if r["kind"] == "event"]
    s = mgr.summary()
    assert s["cache_hits"] == 1 and s["cache_misses"] == 1
    assert mgr.metrics.snapshot()["cache.hits"] == 1


def test_fault_injected_stream_bit_identical_with_tracing():
    oinst = _stream(M=24, seed=4, span=400.0)
    hi = float(oinst.releases.max())
    fault = CoreDown(t=hi / 2 + 0.5, core=2)
    cfg = FabricConfig(rates=RATES, delta=8.0, N=10)
    off = FabricManager(cfg)
    tr = Tracer()
    on = FabricManager(cfg, tracer=tr)
    _drive(off, oinst, fault_after=2, fault=fault)
    _drive(on, oinst, fault_after=2, fault=fault)
    assert np.array_equal(off.ccts(), on.ccts())
    assert _program_tuple(off) == _program_tuple(on)
    # one fault/recover span, with the recovery accounting on it
    recov = [r for r in tr.records if r["name"] == "fault/recover"]
    assert len(recov) == 1 and recov[0]["attrs"]["event"] == "CoreDown"
    assert recov[0]["attrs"]["aborted"] == recov[0]["attrs"]["requeued"]
    assert validate_records(tr.records) == []
    assert tr.open_spans == 0
    # counters agree too (summary has no wall-clock-free guarantee, so
    # compare everything except the timing-derived keys)
    noisy = {k for k in off.summary()
             if "wall" in k or "latency" in k or "per_s" in k}
    s_off = {k: v for k, v in off.summary().items() if k not in noisy}
    s_on = {k: v for k, v in on.summary().items() if k not in noisy}
    assert s_off == s_on


def test_trace_well_formed_under_backpressure_and_bad_fault():
    tr = Tracer()
    mgr = FabricManager(FabricConfig(rates=RATES, delta=8.0, N=4,
                                     max_queue_depth=2), tracer=tr)
    c = Coflow(cid=0, demand=np.eye(4))
    mgr.submit(c, 0.5)
    mgr.submit(c, 0.6)
    with pytest.raises(BackpressureError):
        mgr.submit(c, 0.7)
    mgr.tick(1.0)
    with pytest.raises(ValueError):
        mgr.report_fault(CoreDown(t=0.0, core=99))  # no such core
    assert tr.open_spans == 0
    assert validate_records(tr.records) == []
    # the failed recovery still closed its span, marked as an error
    recov = [r for r in tr.records if r["name"] == "fault/recover"]
    assert len(recov) == 1 and recov[0].get("error") is True
    mgr.flush()
    assert tr.open_spans == 0 and validate_records(tr.records) == []


# ---------------------------------------------------------------------------
# summary(): latency-window coverage is reported honestly
# ---------------------------------------------------------------------------

def test_summary_reports_latency_window_coverage():
    oinst = _stream(M=16, seed=1)
    full = FabricManager(FabricConfig(rates=RATES, delta=8.0, N=10))
    _drive(full, oinst)
    s = full.summary()
    assert s["coflows_finalized"] == oinst.inst.M
    assert s["latency_samples_observed"] == oinst.inst.M
    assert s["latency_samples_retained"] == oinst.inst.M
    assert s["latency_window_coverage"] == 1.0

    small = FabricManager(FabricConfig(rates=RATES, delta=8.0, N=10,
                                       max_latency_samples=8))
    _drive(small, oinst)
    s = small.summary()
    # the window truncates, and summary() says so instead of pretending
    # the percentiles cover the full population
    assert s["latency_samples_observed"] == oinst.inst.M
    assert s["latency_samples_retained"] == 8
    assert s["latency_window_coverage"] == pytest.approx(8 / oinst.inst.M)
    assert s["decision_latency_p99_s"] >= s["decision_latency_p50_s"] >= 0


# ---------------------------------------------------------------------------
# CLI contract
# ---------------------------------------------------------------------------

@pytest.fixture()
def traced_run(tmp_path):
    sink = tmp_path / "trace.jsonl"
    tr = Tracer(sink)
    mgr = FabricManager(FabricConfig(rates=RATES, delta=8.0, N=10),
                        tracer=tr)
    _drive(mgr, _stream(seed=2), n_ticks=4)
    tr.close()
    return sink


def test_cli_summarize_reproduces_phase_breakdown(traced_run, capsys):
    assert obs_main(["summarize", str(traced_run), "--json"]) == 0
    summ = json.loads(capsys.readouterr().out)
    assert FABRIC_PHASES <= set(summ["phases"])
    # per-tick sub-phases nest inside the root: their wall sums below it
    tick_total = summ["phases"]["tick"]["total_s"]
    sub_total = sum(st["total_s"] for name, st in summ["phases"].items()
                    if name.startswith("tick/"))
    assert 0 <= sub_total <= tick_total
    assert summ["top_slow_ticks"]
    assert summ["top_slow_ticks"][0]["attrs"]["core_mask"] == "111"
    # plain-text mode renders the same table without crashing
    assert obs_main(["summarize", str(traced_run)]) == 0
    out = capsys.readouterr().out
    assert "tick/event_loop" in out and "share" in out


def test_cli_validate_exit_codes(traced_run, tmp_path, capsys):
    assert obs_main(["validate", str(traced_run)]) == 0
    assert "OK" in capsys.readouterr().out
    bad = tmp_path / "bad.jsonl"
    rec = {"kind": "span", "name": "tick", "sid": 0, "parent": 7,
           "depth": 1, "ts": 0.0, "dur": -1.0, "attrs": {}}
    bad.write_text(json.dumps(rec) + "\n", encoding="utf-8")
    assert obs_main(["validate", str(bad)]) == 1
    out = capsys.readouterr().out
    assert "dur" in out and "parent sid 7" in out


def test_cli_diff_flags_regressions(traced_run, tmp_path, capsys):
    # synthesize a "regressed" trace: same phases, 10x the duration
    records = load_trace(traced_run)
    slow = tmp_path / "slow.jsonl"
    with open(slow, "w", encoding="utf-8") as fh:
        for r in records:
            r = dict(r)
            if r["kind"] == "span":
                r["dur"] = float(r["dur"]) * 10 + 1.0
            fh.write(json.dumps(r) + "\n")
    assert obs_main(["diff", str(traced_run), str(slow), "--json"]) == 0
    rows = json.loads(capsys.readouterr().out)["phases"]
    by_phase = {r["phase"]: r for r in rows}
    assert by_phase["tick"]["mean_ratio"] > 5
    assert obs_main(["diff", str(traced_run), str(slow),
                     "--fail-over", "2.0"]) == 1
    assert obs_main(["diff", str(traced_run), str(traced_run),
                     "--fail-over", "2.0"]) == 0


def test_diff_phases_handles_new_and_missing():
    old = {"tick": {"count": 2.0, "total_s": 1.0, "mean_s": 0.5}}
    new = {"tick/splice": {"count": 1.0, "total_s": 0.1, "mean_s": 0.1}}
    rows = {r["phase"]: r for r in diff_phases(old, new)}
    assert rows["tick"]["mean_s_new"] == 0.0
    assert rows["tick/splice"]["mean_ratio"] == float("inf")


def test_cli_diff_bench_artifacts(tmp_path, capsys):
    old_d, new_d = tmp_path / "old", tmp_path / "new"
    old_d.mkdir(), new_d.mkdir()
    base = {"overload": {"shed": 10, "wall_s": 1.0},
            "nested": [{"p99": 2.0}], "label": "x"}
    cand = {"overload": {"shed": 14, "wall_s": 1.8},
            "nested": [{"p99": 2.05}]}
    (old_d / "BENCH_overload.json").write_text(json.dumps(base))
    (new_d / "BENCH_overload.json").write_text(json.dumps(cand))

    report = diff_bench(base, cand, threshold=0.10)
    flags = {r["key"]: r["flag"] for r in report["rows"]}
    assert flags["overload.shed"] == "changed"       # +40% > 10%
    assert flags["overload.wall_s"] == ""            # noisy key, < 2x
    assert flags["nested[0].p99"] == ""              # +2.5% < 10%
    assert report["n_flagged"] == 1                  # strings are ignored

    assert obs_main(["diff-bench", str(old_d), str(new_d), "--json"]) == 0
    out = json.loads(capsys.readouterr().out)
    assert out["BENCH_overload.json"]["n_flagged"] == 1
    assert obs_main(["diff-bench", str(old_d), str(new_d),
                     "--fail-on-flag"]) == 1
    capsys.readouterr()
    empty = tmp_path / "empty"
    empty.mkdir()
    assert obs_main(["diff-bench", str(empty), str(new_d)]) == 2


def test_cli_diff_bench_floors(tmp_path, capsys):
    old_d, new_d = tmp_path / "old", tmp_path / "new"
    old_d.mkdir(), new_d.mkdir()
    base = {"data": {"rows": [{"loc_reuse_mean": 0.45}]}, "wall_s": 1.0}

    # check_floors directly: pass, below-floor, and missing-leaf cases.
    assert check_floors(base, {"data.rows[0].loc_reuse_mean": 0.4}) == []
    msgs = check_floors(base, {"data.rows[0].loc_reuse_mean": 0.5,
                               "data.rows[0].gone": 0.1})
    assert len(msgs) == 2
    assert any("fell below committed floor 0.5" in m for m in msgs)
    assert any("missing from candidate artifact" in m for m in msgs)

    (old_d / "BENCH_overload.json").write_text(json.dumps(base))
    (new_d / "BENCH_overload.json").write_text(json.dumps(base))
    floors_ok = tmp_path / "FLOORS.json"
    floors_ok.write_text(json.dumps({
        "_comment": "strings are skipped, never treated as floors",
        "BENCH_overload.json": {"data.rows[0].loc_reuse_mean": 0.4}}))
    assert obs_main(["diff-bench", str(old_d), str(new_d),
                     "--floors", str(floors_ok)]) == 0
    capsys.readouterr()

    # a candidate below the committed floor fails even though the leaf
    # diff itself is under threshold
    worse = {"data": {"rows": [{"loc_reuse_mean": 0.38}]}, "wall_s": 1.0}
    (new_d / "BENCH_overload.json").write_text(json.dumps(worse))
    assert obs_main(["diff-bench", str(old_d), str(new_d),
                     "--floors", str(floors_ok), "--threshold", "0.5"]) == 1
    assert "FLOOR BREACH" in capsys.readouterr().err

    # a floors entry whose artifact pair never materialized is a breach
    floors_orphan = tmp_path / "FLOORS_orphan.json"
    floors_orphan.write_text(json.dumps(
        {"BENCH_missing.json": {"data.x": 1.0}}))
    (new_d / "BENCH_overload.json").write_text(json.dumps(base))
    assert obs_main(["diff-bench", str(old_d), str(new_d),
                     "--floors", str(floors_orphan)]) == 1
    assert "no baseline/candidate pair" in capsys.readouterr().err


def test_cli_export_chrome(traced_run, tmp_path):
    out = tmp_path / "chrome.json"
    assert obs_main(["export-chrome", str(traced_run), "-o", str(out)]) == 0
    doc = json.loads(out.read_text(encoding="utf-8"))
    assert doc["traceEvents"]
    ts = [e["ts"] for e in doc["traceEvents"]]
    assert ts == sorted(ts)


def test_module_entry_point_smoke(traced_run):
    proc = subprocess.run(
        [sys.executable, "-m", "repro.obs", "summarize", str(traced_run)],
        capture_output=True, text=True, cwd=REPO,
        env={"PYTHONPATH": str(REPO / "src"), "PATH": "/usr/bin:/bin"})
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "tick" in proc.stdout

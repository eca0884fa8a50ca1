"""The reduction from a profiler trace to device metrics.

One hand-made trace with known answers, and one recorded on a TPU v5e
(``fixtures/trace_n16.json``: a few requests of the
``paper-n16-k3.oneshot-m100`` cell, as ``devtrace.capture`` keeps them),
checked against a plain sweep over the same events.
"""
import json
from pathlib import Path

import pytest

from yardstick import devtrace, work

FIXTURE = Path(__file__).with_name("fixtures") / "trace_n16.json"


def test_hand_made_trace():
    trace = {"chips": 1,
             "device": [[0, "fusion", 10, 20], [0, "assign_kernel", 15, 30],
                        [0, "copy", 50, 55]],
             "host": [["window", 0, 100], ["request", 5, 95],
                      ["compile", 30, 45], ["event_loop", 60, 90]]}
    assert devtrace.union([(10, 20), (15, 30), (50, 55)]) == [(10, 30), (50, 55)]
    assert devtrace.busy_ns(trace) == 25
    assert devtrace.op_ns(trace, work.is_assign_kernel) == 15
    assert devtrace.top_ops(trace) == [["assign_kernel", 15e-9],
                                       ["fusion", 10e-9], ["copy", 5e-9]]
    # idle: [0,10) client 5 + request 5; [30,50) compile 15 + request 5;
    # [55,100) request 10 + event_loop 30 + client 5
    idle = dict(devtrace.idle_by_host_span(trace))
    assert idle == pytest.approx({"event_loop": 30e-9, "request": 20e-9,
                                  "compile": 15e-9, "client": 10e-9})
    assert sum(idle.values()) == pytest.approx(75e-9)


def _sweep_busy(intervals):
    """Busy time by a plain sweep over every boundary."""
    pts = sorted({t for s, e in intervals for t in (s, e)})
    return sum(b - a for a, b in zip(pts, pts[1:])
               if any(s <= a and b <= e for s, e in intervals))


@pytest.fixture(scope="module")
def recorded():
    return json.loads(FIXTURE.read_text())


def test_recorded_trace_busy_and_kernel(recorded):
    dev = [(s, e) for _, _, s, e in recorded["device"]]
    assert devtrace.busy_ns(recorded) == _sweep_busy(dev)
    kernel = [e - s for _, n, s, e in recorded["device"]
              if work.is_assign_kernel(n)]
    assert kernel, "the kernel is not found by its name in the trace"
    assert devtrace.op_ns(recorded, work.is_assign_kernel) == sum(kernel)


def test_recorded_trace_idle_is_window_less_busy(recorded):
    w0, w1 = devtrace.window(recorded)
    dev = [(max(s, w0), min(e, w1)) for _, _, s, e in recorded["device"]
           if e > w0 and s < w1]
    idle = devtrace.idle_by_host_span(recorded, n=100)
    assert sum(v for _, v in idle) == pytest.approx(
        ((w1 - w0) - _sweep_busy(dev)) / 1e9, rel=1e-9)
    names = {n for n, _ in idle}
    assert {"compile", "event_loop"} <= names

"""Capture a profiler trace of the window and reduce it to device metrics.

``capture()`` records the window with JAX's profiler (no Python tracer:
only the runtime's own events and the benchmark's ``bench/*`` annotations),
reads the ``.xplane.pb`` back with ``jax.profiler.ProfileData`` and keeps
two plain lists: the device's operations and the benchmark's host spans,
both on the profiler's clock in nanoseconds. The trace files themselves
are deleted. The reductions below take those lists, so they are checked
on a small recorded trace (``bench/tests/fixtures``) without a chip.
"""
from __future__ import annotations

import glob
import os
import shutil
import tempfile
import time

#: The device line that holds one event per XLA operation (the others,
#: modules and steps, span the same time again).
OPS_LINE = "XLA Ops"


def _load(path: str) -> dict:
    import jax

    pd = jax.profiler.ProfileData.from_file(path)
    device: list[list] = []
    host: list[list] = []
    n_planes = 0
    for plane in pd.planes:
        lines = list(plane.lines)
        if plane.name.startswith("/device:") and "TPU" in plane.name \
                and "SparseCore" not in plane.name:
            ops = [ln for ln in lines if ln.name == OPS_LINE]
            if not ops:
                continue
            n_planes += 1
            for ln in ops:
                for ev in ln.events:
                    device.append([n_planes - 1, ev.name, int(ev.start_ns),
                                   int(ev.start_ns + ev.duration_ns)])
        elif plane.name.startswith("/host:"):
            for ln in lines:
                for ev in ln.events:
                    if ev.name.startswith("bench/"):
                        host.append([ev.name[len("bench/"):], int(ev.start_ns),
                                     int(ev.start_ns + ev.duration_ns)])
    return {"chips": n_planes, "device": device, "host": host}


class capture:
    """Context manager: profile the block; ``.trace`` holds the result."""

    def __enter__(self) -> "capture":
        import jax

        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> bool:
        import jax

        jax.profiler.stop_trace()
        self.window_s = time.perf_counter() - self.t0
        try:
            files = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                              recursive=True)
            self.trace = _load(files[0]) if files else \
                {"chips": 0, "device": [], "host": []}
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return False


# -- reductions ---------------------------------------------------------------

def union(intervals: list) -> list[tuple[int, int]]:
    """Merge ``(start, end)`` intervals into disjoint, sorted ones."""
    out: list[list[int]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def window(trace: dict) -> tuple[int, int]:
    """The traced window on the profiler's clock: the ``bench/window``
    span."""
    (w0, w1), = [(s, e) for name, s, e in trace["host"] if name == "window"]
    return w0, w1


def busy_ns(trace: dict) -> float:
    """Union of the device's operation intervals, averaged over chips."""
    chips = max(1, trace["chips"])
    total = 0
    for c in range(chips):
        total += sum(e - s for s, e in union(
            [(s, e) for chip, _, s, e in trace["device"] if chip == c]))
    return total / chips


def op_ns(trace: dict, match) -> int:
    """Summed device time of the operations whose name ``match`` accepts."""
    return sum(e - s for _, name, s, e in trace["device"] if match(name))


def op_name(event_name: str) -> str:
    """An operation's name without its HLO text: ``%fusion.3 = f32[..] ...``
    is ``%fusion.3``, the same for every shape it ran at."""
    return event_name.split(" = ", 1)[0]


def top_ops(trace: dict, n: int = 10) -> list[list]:
    """The ``n`` operations that took most device time, in seconds."""
    by: dict[str, int] = {}
    for _, name, s, e in trace["device"]:
        by[op_name(name)] = by.get(op_name(name), 0) + (e - s)
    top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]


def idle_by_host_span(trace: dict, n: int = 10) -> list[list]:
    """Device idle time in the window, named by the innermost host span
    running at each moment of it (``"client"`` outside every request: the
    benchmark building the next one); the ``n`` largest, in seconds."""
    w0, w1 = window(trace)
    busy = union([(max(s, w0), min(e, w1)) for _, _, s, e in trace["device"]
                  if e > w0 and s < w1])
    gaps, t = [], w0
    for s, e in busy:
        if s > t:
            gaps.append((t, s))
        t = max(t, e)
    if t < w1:
        gaps.append((t, w1))
    # cut each gap at every host span boundary inside it, then give each
    # piece to the shortest span covering it
    spans = [(s, e, name) for name, s, e in trace["host"] if name != "window"]
    cuts = sorted({t for s, e, _ in spans for t in (s, e)})
    by: dict[str, int] = {}
    for g0, g1 in gaps:
        pts = [g0] + [c for c in cuts if g0 < c < g1] + [g1]
        for a, b in zip(pts, pts[1:]):
            mid = (a + b) / 2
            inside = [(e - s, name) for s, e, name in spans if s <= mid < e]
            name = min(inside)[1] if inside else "client"
            by[name] = by.get(name, 0) + (b - a)
    top = sorted(by.items(), key=lambda kv: -kv[1])[:n]
    return [[name, ns / 1e9] for name, ns in top]

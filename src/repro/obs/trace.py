"""Phase-level span tracer for the fabric planes (engine/service/fault).

One :class:`Tracer` records nested **spans** (named intervals with typed
attributes) and instant **events** into an in-memory buffer, optionally
flushed to a JSONL sink, and exportable as a Chrome-trace / Perfetto
``traceEvents`` document. The span taxonomy the fabric emits (see
DESIGN.md §Observability):

Streaming plane (``FabricManager.tick``):

  ``tick``                 one ``FabricManager`` service tick (root)
  ``tick/admit``           admission-queue drain under the flow budget
  ``tick/assign``          batch registration + core assignment
  ``tick/splice``          delta-scheduling cache splice against the
                           incremental component index (``reused``,
                           ``recomputed``, ``invalidated`` — rows a fault
                           staled — plus ``components_total`` /
                           ``components_touched``)
  ``tick/event_loop``      the vectorized event loop over touched rows
                           (``rows``, ``events``, ``candidates``: pending
                           flows examined at events, see
                           ``engine.LoopCounts``)
  ``tick/program_emit``    circuit-program compilation (+ referee)
  ``fault/recover``        one fault application (abort/requeue counts +
                           ``invalidated``: tentative rows the scoped
                           invalidation staled, see DESIGN.md
                           §Delta-scheduling)
  ``cache/purge``          one-shot program-cache purge (event)

One-shot plane (``FabricManager.schedule_instance``; the engine's free
functions record into :func:`current_tracer`, which the manager sets to
its own tracer for the call):

  ``oneshot``              the whole request (``coflows``, ``flows``,
                           ``hit``, ``compiles``: XLA compilations during
                           the call that the persistent cache did not
                           answer, see :func:`compile_count`;
                           ``materialized``: ``ScheduledFlow`` records
                           built during the call, 0 on the columnar path)
  ``oneshot/key``          fabric fingerprint, ``instance_key``, lookup
  ``oneshot/order``        ``order_coflows`` / ``online_orders``
  ``oneshot/extract``      ``extract_flows`` (``flows``)
  ``oneshot/assign``       core assignment, either implementation
                           (``flows``, ``impl``: ``pallas`` / ``numpy``)
  ``oneshot/assign/put``   the kernel inputs' casts and transfers
  ``oneshot/assign/launch``  the jitted kernel call, which returns before
                           the device finishes (``padded_flows``)
  ``oneshot/assign/fetch`` the wait for the device and the read-back
  ``oneshot/event_loop``   ``_times_for_table`` (``events``: heap pops,
                           ``candidates``: pending flows examined at
                           events, see ``engine.LoopCounts``)
  ``oneshot/schedule``     ``_schedule_from_times`` (the flow columns)
  ``oneshot/emit``         ``compile_schedule`` (``segments``)
  ``oneshot/cache``        relabelling and the cache put

Profiler bridge: whenever a JAX profiler session is active
(``jax.profiler.TraceAnnotation.is_enabled()``), every span — of a
recording :class:`Tracer` and of :data:`NULL_TRACER` alike — is also a
``TraceAnnotation`` named ``fabric/<span>``, its attributes riding along
as the annotation's metadata, so it lands in the profiler trace on the
device trace's clock. In-memory records keep :mod:`repro.obs.clock`
timestamps. JAX is never imported for this: before anything imports
``jax.profiler`` no session can be active.

Determinism contract: the tracer only *observes* — all timestamps come
from the sanctioned :mod:`repro.obs.clock` boundary and no instrumented
code path reads a span back, so schedules are bit-identical with tracing
on or off (``tests/test_obs.py`` asserts this differentially, including
a fault-injected run, and ``tests/test_obs_profiler.py`` with a profiler
session on).

Overhead contract: the disabled path is allocation-free. The global
default is :data:`NULL_TRACER`, whose ``span()`` returns one shared
no-op span object (after one ``is_enabled()`` check) and whose
``event()`` returns immediately; call sites compute attributes only
behind ``span.live`` / ``tracer.enabled`` guards, so a manager with
tracing off does no per-tick tracing work beyond a few attribute loads
and no-op calls.
"""
from __future__ import annotations

import json
import sys
from pathlib import Path
from typing import IO, Iterator

from .clock import now

__all__ = [
    "Span", "Tracer", "NullTracer", "NULL_TRACER",
    "current_tracer", "set_tracer", "to_chrome_trace", "compile_count",
]

#: Name prefix of the spans' copies in a profiler trace.
PROFILER_PREFIX = "fabric/"

#: ``jax.profiler.TraceAnnotation`` once ``jax.profiler`` is imported.
_ANNOTATION: type | None = None


def _profiler_annotation(name: str) -> object | None:
    """A ``fabric/<name>`` annotation while a profiler session is active,
    else ``None`` (one ``is_enabled()`` check)."""
    global _ANNOTATION
    cls = _ANNOTATION
    if cls is None:
        mod = sys.modules.get("jax.profiler")
        if mod is None:
            return None  # nothing imported jax.profiler: no session
        cls = _ANNOTATION = mod.TraceAnnotation
    if not cls.is_enabled():  # type: ignore[attr-defined]
        return None
    return cls(PROFILER_PREFIX + name)


#: JAX's monitoring events behind :func:`compile_count`.
_BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
_CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"
#: [backend compiles requested, persistent-cache hits] since registration
_COMPILES: list[int] | None = None


def compile_count() -> int:
    """XLA compilations in this process that the persistent compilation
    cache did not answer, counted from JAX's monitoring events since the
    first call (which imports ``jax.monitoring`` and registers the one
    listener); a difference of two calls counts what ran between them."""
    global _COMPILES
    if _COMPILES is None:
        import jax.monitoring as monitoring

        counts = _COMPILES = [0, 0]

        def on_duration(event: str, _secs: float, **_kw: object) -> None:
            if event == _BACKEND_COMPILE_EVENT:
                counts[0] += 1

        def on_event(event: str, **_kw: object) -> None:
            if event == _CACHE_HIT_EVENT:
                counts[1] += 1

        monitoring.register_event_duration_secs_listener(on_duration)
        monitoring.register_event_listener(on_event)
    return _COMPILES[0] - _COMPILES[1]


def _jsonable_attr(v: object) -> object:
    """Coerce one span attribute to a JSON-safe scalar (json has no inf)."""
    if isinstance(v, bool) or v is None or isinstance(v, (int, str)):
        return v
    if isinstance(v, float):
        return v if v == v and abs(v) != float("inf") else repr(v)
    try:
        # numpy scalars and other number-likes
        return _jsonable_attr(float(v))  # type: ignore[arg-type]
    except (TypeError, ValueError):
        return repr(v)


class Span:
    """One open interval; closes (and records itself) on ``__exit__``.

    ``live`` is True on real spans and False on the shared no-op span —
    instrumented code guards attribute computation behind it so the
    disabled path stays free. ``ann`` is the span's profiler copy (see
    the module docstring), or ``None`` outside a profiler session.
    """

    __slots__ = ("_tracer", "name", "sid", "parent", "depth", "t0", "attrs",
                 "_ann")

    live: bool = True

    def __init__(self, tracer: "Tracer", name: str, sid: int,
                 parent: int | None, depth: int,
                 ann: object | None = None) -> None:
        self._tracer = tracer
        self.name = name
        self.sid = sid
        self.parent = parent
        self.depth = depth
        self._ann = ann
        self.t0 = now()
        self.attrs: dict[str, object] = {}

    def set(self, **attrs: object) -> "Span":
        """Attach typed attributes (recorded when the span closes)."""
        self.attrs.update(attrs)
        if self._ann is not None:
            _annotate(self._ann, attrs)
        return self

    def __enter__(self) -> "Span":
        if self._ann is not None:
            self._ann.__enter__()  # type: ignore[attr-defined]
        return self

    def __exit__(self, exc_type: object, exc: object, tb: object) -> bool:
        if self._ann is not None:
            self._ann.__exit__(exc_type, exc, tb)  # type: ignore[attr-defined]
        self._tracer._close(self, error=exc_type is not None)
        return False


def _annotate(ann: object, attrs: dict[str, object]) -> None:
    ann.set_metadata(  # type: ignore[attr-defined]
        **{k: _jsonable_attr(v) for k, v in attrs.items()})


class _ProfilerSpan:
    """A span of :data:`NULL_TRACER` during a profiler session: the
    profiler copy alone, nothing kept in memory."""

    __slots__ = ("_ann",)

    live: bool = True

    def __init__(self, ann: object) -> None:
        self._ann = ann

    def set(self, **attrs: object) -> "_ProfilerSpan":
        _annotate(self._ann, attrs)
        return self

    def __enter__(self) -> "_ProfilerSpan":
        self._ann.__enter__()  # type: ignore[attr-defined]
        return self

    def __exit__(self, exc_type: object, exc: object, tb: object) -> bool:
        self._ann.__exit__(exc_type, exc, tb)  # type: ignore[attr-defined]
        return False


class _NullSpan:
    """The shared no-op span: one instance, zero per-call allocation."""

    __slots__ = ()

    live: bool = False

    def set(self, **attrs: object) -> "_NullSpan":
        return self

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, exc_type: object, exc: object, tb: object) -> bool:
        return False


NULL_SPAN = _NullSpan()


class Tracer:
    """Recording tracer: nested spans + events -> JSONL / Chrome trace.

    ``sink`` may be a path (JSONL written on ``flush()``/``close()``) or
    an open text file object; ``None`` keeps records in memory only
    (``records`` stays available either way).
    """

    enabled: bool = True

    def __init__(self, sink: str | Path | IO[str] | None = None) -> None:
        self.records: list[dict[str, object]] = []
        self._stack: list[Span] = []
        self._next_sid = 0
        self._flushed = 0
        self._sink_path: Path | None = None
        self._sink_file: IO[str] | None = None
        if isinstance(sink, (str, Path)):
            self._sink_path = Path(sink)
        elif sink is not None:
            self._sink_file = sink

    # -- recording ----------------------------------------------------------
    def span(self, name: str) -> Span:
        """Open a nested span; close it with ``with`` (exception-safe)."""
        sid = self._next_sid
        self._next_sid += 1
        parent = self._stack[-1].sid if self._stack else None
        sp = Span(self, name, sid, parent, depth=len(self._stack),
                  ann=_profiler_annotation(name))
        self._stack.append(sp)
        return sp

    def event(self, name: str, **attrs: object) -> None:
        """Record one instant event at the current nesting depth."""
        parent = self._stack[-1].sid if self._stack else None
        sid = self._next_sid
        self._next_sid += 1
        self.records.append({
            "kind": "event", "name": name, "sid": sid, "parent": parent,
            "depth": len(self._stack), "ts": now(),
            "attrs": {k: _jsonable_attr(v) for k, v in attrs.items()},
        })

    def _close(self, span: Span, error: bool = False) -> None:
        # Pop to (and including) `span`. With-statement nesting guarantees
        # LIFO order; popping defensively keeps the stack well-formed even
        # if an unclosed inner span leaks past an exception handler.
        while self._stack:
            top = self._stack.pop()
            if top is span:
                break
        rec: dict[str, object] = {
            "kind": "span", "name": span.name, "sid": span.sid,
            "parent": span.parent, "depth": span.depth,
            "ts": span.t0, "dur": now() - span.t0,
            "attrs": {k: _jsonable_attr(v) for k, v in span.attrs.items()},
        }
        if error:
            rec["error"] = True
        self.records.append(rec)

    @property
    def open_spans(self) -> int:
        """Spans currently open (0 when nesting is well-formed at rest)."""
        return len(self._stack)

    # -- sinks --------------------------------------------------------------
    def flush(self) -> None:
        """Append unflushed records to the sink (no-op without one)."""
        pending = self.records[self._flushed:]
        if not pending:
            return
        if self._sink_path is not None:
            with open(self._sink_path, "a", encoding="utf-8") as fh:
                for rec in pending:
                    fh.write(json.dumps(rec, sort_keys=True) + "\n")
            self._flushed = len(self.records)
        elif self._sink_file is not None:
            for rec in pending:
                self._sink_file.write(json.dumps(rec, sort_keys=True) + "\n")
            self._flushed = len(self.records)

    def close(self) -> None:
        self.flush()

    def __enter__(self) -> "Tracer":
        return self

    def __exit__(self, exc_type: object, exc: object, tb: object) -> bool:
        self.close()
        return False

    def to_chrome_trace(self) -> dict[str, object]:
        """Chrome-trace / Perfetto ``traceEvents`` document."""
        return to_chrome_trace(self.records)


class NullTracer(Tracer):
    """The disabled tracer: every operation is a no-op.

    ``span()`` returns the one shared :data:`NULL_SPAN` instance, so the
    disabled hot path allocates nothing; ``records`` stays empty. During a
    profiler session it returns a span that records into the profiler
    trace only.
    """

    enabled = False

    def __init__(self) -> None:
        super().__init__(sink=None)

    def span(self, name: str) -> Span:
        ann = _profiler_annotation(name)
        if ann is None:
            return NULL_SPAN  # type: ignore[return-value]
        return _ProfilerSpan(ann)  # type: ignore[return-value]

    def event(self, name: str, **attrs: object) -> None:
        return None

    def flush(self) -> None:
        return None


NULL_TRACER = NullTracer()

#: process-wide default tracer; ``FabricManager`` picks it up at
#: construction when not handed one explicitly.
_CURRENT: Tracer = NULL_TRACER


def current_tracer() -> Tracer:
    """The process-wide default tracer (``NULL_TRACER`` unless set)."""
    return _CURRENT


def set_tracer(tracer: Tracer | None) -> Tracer:
    """Install the process-wide default tracer; returns the previous one.

    ``None`` restores :data:`NULL_TRACER`.
    """
    global _CURRENT
    prev = _CURRENT
    _CURRENT = NULL_TRACER if tracer is None else tracer
    return prev


def _chrome_events(records: list[dict[str, object]]
                   ) -> Iterator[dict[str, object]]:
    for rec in records:
        ts_us = float(rec.get("ts", 0.0)) * 1e6  # type: ignore[arg-type]
        base: dict[str, object] = {
            "name": rec.get("name", "?"), "pid": 0, "tid": 0,
            "ts": ts_us, "args": rec.get("attrs", {}),
        }
        if rec.get("kind") == "span":
            base["ph"] = "X"
            base["dur"] = float(rec.get("dur", 0.0)) * 1e6  # type: ignore[arg-type]
        else:
            base["ph"] = "i"
            base["s"] = "t"
        yield base


def to_chrome_trace(records: list[dict[str, object]]) -> dict[str, object]:
    """Convert JSONL records to a Chrome-trace document.

    Load the result in Perfetto (https://ui.perfetto.dev) or
    ``chrome://tracing`` to see the per-phase flame view of a run.
    """
    return {
        "traceEvents": sorted(_chrome_events(records),
                              key=lambda e: float(e["ts"])),  # type: ignore[arg-type]
        "displayTimeUnit": "ms",
    }

"""Host spans and compile counts, taken from the benchmark's side.

``Spans`` wraps functions of the program at their module attribute: each
call is timed on the host clock and, inside the profiler's window, shows as
a ``jax.profiler.TraceAnnotation`` named ``bench/<label>`` on the same clock
as the device trace. A function that a later change renames is not found:
its label records nothing, and the metrics that read it are left out.

``Compiles`` counts XLA compilations from JAX's own monitoring events: each
``backend_compile_duration`` event is one request for a compiled program,
less those that the persistent cache answered.
"""
from __future__ import annotations

import functools
import importlib
import time
from typing import Callable

BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class Spans:
    """Host spans around the program's layer calls, kept in memory."""

    def __init__(self) -> None:
        self.records: list[tuple[str, int, int]] = []  # label, t0, t1 (ns)
        self.armed = False
        self._undo: list[Callable[[], None]] = []

    def wrap(self, target: str, label: str) -> None:
        """Time every call of ``module:attr`` under ``label``."""
        import jax

        mod_name, attr = target.split(":")
        try:
            mod = importlib.import_module(mod_name)
            fn = getattr(mod, attr)
        except (ImportError, AttributeError):
            return

        @functools.wraps(fn)
        def timed(*args, **kwargs):
            if not self.armed:
                return fn(*args, **kwargs)
            t0 = time.perf_counter_ns()
            try:
                with jax.profiler.TraceAnnotation(f"bench/{label}"):
                    return fn(*args, **kwargs)
            finally:
                self.records.append((label, t0, time.perf_counter_ns()))

        setattr(mod, attr, timed)
        self._undo.append(lambda: setattr(mod, attr, fn))

    def span(self, label: str):
        """A span of the benchmark's own (a whole request, the window)."""
        return _Span(self, label)

    def restore(self) -> None:
        while self._undo:
            self._undo.pop()()

    def of(self, label: str) -> list[tuple[int, int]]:
        return [(t0, t1) for name, t0, t1 in self.records if name == label]


class _Span:
    def __init__(self, spans: Spans, label: str) -> None:
        self.spans, self.label = spans, label

    def __enter__(self):
        import jax

        self.t0 = time.perf_counter_ns()
        self.ann = jax.profiler.TraceAnnotation(f"bench/{self.label}")
        self.ann.__enter__()
        return self

    def __exit__(self, *exc):
        self.ann.__exit__(*exc)
        if self.spans.armed:
            self.spans.records.append(
                (self.label, self.t0, time.perf_counter_ns()))
        return False


class Compiles:
    """XLA compilations that the persistent cache did not answer."""

    def __init__(self) -> None:
        import jax.monitoring as monitoring

        self.armed = False
        self.requested = 0
        self.cache_hits = 0
        monitoring.register_event_duration_secs_listener(self._on_duration)
        monitoring.register_event_listener(self._on_event)

    def _on_duration(self, event: str, _secs: float, **_kw) -> None:
        if self.armed and event == BACKEND_COMPILE_EVENT:
            self.requested += 1

    def _on_event(self, event: str, **_kw) -> None:
        if self.armed and event == CACHE_HIT_EVENT:
            self.cache_hits += 1

    @property
    def compiled(self) -> int:
        return self.requested - self.cache_hits

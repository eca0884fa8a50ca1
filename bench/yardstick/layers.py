"""Per-request sums of the host spans that the per-layer readers share."""
from __future__ import annotations

#: The program's layers inside one request, as ``run.SPAN_TARGETS`` labels.
LAYERS = ("extract", "assign", "event_loop", "schedule", "emit")


def span_ns(view, *labels: str) -> int | None:
    """Summed duration of the spans of ``labels``; ``None`` where one of
    them recorded nothing (its function was not found or never ran)."""
    if view.spans is None:
        return None
    total = 0
    for label in labels:
        spans = view.spans.of(label)
        if not spans:
            return None
        total += sum(t1 - t0 for t0, t1 in spans)
    return total


def n_flows(view) -> int:
    return sum(view.result["flows"])


def n_requests(view) -> int:
    return len(view.result["flows"])

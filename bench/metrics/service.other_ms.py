"""service.other_ms: host time of a request outside the program's layers.

The request's wall time (a ``bench/request`` span around
``FabricManager.schedule_instance``) less the spans of its layers (flow
extraction, assignment, event loop, schedule and program emission): the
cache key, the ordering, the relabelling and the glue. ms per request.
"""
from yardstick import layers


def read(view):
    inner = layers.span_ns(view, *layers.LAYERS)
    whole = layers.span_ns(view, "request")
    if inner is None or whole is None:
        return None
    return (whole - inner) / layers.n_requests(view) / 1e6

"""assign.host_ms: host time per request of the assignment call,
``engine._pallas_choices``: tracing, compiling, transfers, the kernel and
the read-back (ms)."""
from yardstick import layers


def read(view):
    ns = layers.span_ns(view, "assign")
    return None if ns is None else ns / layers.n_requests(view) / 1e6

"""engine.event_loop_us_per_flow: host time per flow of the scheduling
phase, ``engine._times_for_table`` and its work-conserving event loop (us)."""
from yardstick import layers


def read(view):
    ns = layers.span_ns(view, "event_loop")
    return None if ns is None else ns / layers.n_flows(view) / 1e3

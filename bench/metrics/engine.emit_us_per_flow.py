"""engine.emit_us_per_flow: host time per flow of turning the schedule's
times into a program: ``engine._schedule_from_times`` (one record per flow)
and ``service.compile_schedule`` (us)."""
from yardstick import layers


def read(view):
    ns = layers.span_ns(view, "schedule", "emit")
    return None if ns is None else ns / layers.n_flows(view) / 1e3

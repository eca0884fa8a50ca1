"""engine.extract_us_per_flow: ``extract_flows`` host time per flow (us)."""
from yardstick import layers


def read(view):
    ns = layers.span_ns(view, "extract")
    return None if ns is None else ns / layers.n_flows(view) / 1e3

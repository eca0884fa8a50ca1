"""kernel.device_ns_per_flow: device time of the assignment kernel per flow,
summed over its events in the profiler trace (ns)."""
from yardstick import devtrace, layers, work


def read(view):
    if view.trace is None:
        return None
    ns = devtrace.op_ns(view.trace, work.is_assign_kernel)
    return ns / layers.n_flows(view) if ns else None

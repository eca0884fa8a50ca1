"""coflow_assign_roofline: the assignment kernel's share of its memory
roofline.

The least time the greedy's HBM traffic (``yardstick.work``: 16 bytes a
flow, counted from the flows) needs at the chip's published HBM bandwidth
(``yardstick/peaks.json``), over the kernel's device time in the trace (%).
"""
from yardstick import devtrace, layers, work


def read(view):
    if view.trace is None or view.peaks is None:
        return None
    ns = devtrace.op_ns(view.trace, work.is_assign_kernel)
    if not ns:
        return None
    least_s = work.roofline_seconds(work.kernel_bytes(layers.n_flows(view)),
                                    view.peaks)
    return 100.0 * least_s / (ns / 1e9)

"""The assignment kernel's work, counted from the flow count.

The count is the algorithm's (the tau-aware greedy of the paper's
Algorithm 1, lines 5-17), not the kernel's layout of it: it depends on the
number of flows F only, so a kernel that keeps its state differently, or
does less per flow, is held to the same count. The HBM traffic is the
kernel's three inputs (ingress, egress, size) and one output (the core),
4 bytes each.

Only the memory bound is counted. The greedy's arithmetic (about 13
float32 vector operations a flow and core) runs on the vector unit, whose
peak Google does not publish for the v5e (``peaks.json``); the matrix
units' peak would understate that bound many times over.
"""
from __future__ import annotations

BYTES_PER_FLOW = 16


def kernel_bytes(n_flows: int) -> float:
    """HBM bytes of one greedy pass over ``n_flows``."""
    return float(BYTES_PER_FLOW * n_flows)


def roofline_seconds(nbytes: float, peaks: dict) -> float:
    """Least time moving ``nbytes`` needs at the device's HBM peak."""
    return nbytes / peaks["hbm_bytes_per_s"]


#: How the assignment kernel's events are named in the device trace.
KERNEL_NAME_PARTS = ("assign_kernel", "coflow_assign")


def is_assign_kernel(op_name: str) -> bool:
    return any(part in op_name for part in KERNEL_NAME_PARTS)

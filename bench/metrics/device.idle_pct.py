"""device.idle_pct: share of the traced window in which no operation ran on
the device (1 - the union of the device's operation intervals over the
window), averaged over the chips used (%)."""
from yardstick import devtrace


def read(view):
    if view.trace is None or not view.window_s or not view.trace["device"]:
        return None
    return 100.0 * (1.0 - devtrace.busy_ns(view.trace) / 1e9 / view.window_s)

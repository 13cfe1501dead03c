"""device_idle_pct: 1 - union of device-operation intervals over the traced
window, averaged over the chips."""

from perfbench import xplane


def read(r):
    bw = r.trace and xplane.busy_and_window_s(r.trace)
    return 100.0 * (1.0 - bw[0] / bw[1]) if bw else None

"""device_step_ms: device busy time of one step (union of the operations
started inside a bench/step span, chip 0), median over the traced steps."""

from perfbench import xplane


def read(r):
    return xplane.device_step_ms(r.trace) if r.trace and r.trace.ops else None

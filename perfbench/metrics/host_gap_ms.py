"""host_gap_ms: median gap on the device between the end of one
bench/step's device work and the start of the next that no bench/data or
bench/ckpt span covers: float(loss), train.report and the next dispatch."""

from perfbench import xplane


def read(r):
    return xplane.host_gap_ms(r.trace) if r.trace and r.trace.ops else None

"""collective_ms: time per step that partitioner-inserted collectives
(all-gather, reduce-scatter, all-reduce) are in flight on chip 0."""

from perfbench import xplane


def read(r):
    out = (xplane.collective_ms_and_exposed_pct(r.trace)
           if r.trace and r.trace.ops else None)
    return out[0] if out else None

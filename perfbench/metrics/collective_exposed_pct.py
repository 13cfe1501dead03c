"""collective_exposed_pct: share of collective_ms during which no other
operation runs on that chip."""

from perfbench import xplane


def read(r):
    out = (xplane.collective_ms_and_exposed_pct(r.trace)
           if r.trace and r.trace.ops else None)
    return out[1] if out else None

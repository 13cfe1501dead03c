"""step_trace_lower_s: the train step's tracing and lowering, over every
compilation of it: Python's share, paid on a cache hit as on a miss; what
stacked layers or a jitted pass shortens."""

from perfbench import setupspans


def read(r):
    return setupspans.step_seconds(r, setupspans.STEP_PARTS)

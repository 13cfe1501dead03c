"""step_backend_s: the ``backend`` part of the train step's compilations:
XLA's compile where the persistent cache missed, the entry's load where it
hit (``step_cache_hit_pct`` says which)."""

from perfbench import setupspans


def read(r):
    return setupspans.step_seconds(r, ("backend",))

"""step_cache_hit_pct: 100 x the train step's compilations that the
persistent cache answered with a hit, over all of them: 100 or 0, 50 where
one of two missed. A count, not a time: the variable that tells two runs'
``setup_s`` apart."""

from perfbench import setupspans


def read(r):
    return setupspans.step_cache_hit_pct(r)

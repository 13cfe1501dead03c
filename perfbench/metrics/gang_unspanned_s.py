"""gang_unspanned_s: ``gang_start_s`` less the union of every ``gang/*`` and
``worker/*`` span of driver and workers between ``gang/placement``'s start
and ``gang/loop``'s end: what of the gang's start no span covers. The
account's own check."""

from perfbench import clusterspans


def read(r):
    return clusterspans.gang_unspanned_s(r)

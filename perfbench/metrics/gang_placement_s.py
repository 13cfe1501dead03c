"""gang_placement_s: the driver's span ``gang/placement``:
``placement_group(bundles)`` -> ``pg.wait`` returned."""

from perfbench import clusterspans


def read(r):
    return clusterspans.span_s(r, "driver", "gang/placement")

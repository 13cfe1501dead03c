"""chip_wait_s: the train worker's span ``gang/chip_wait``: the wait for this
host's chips before jax opens them (``train/backend.py:_wait_for_chips``)."""

from perfbench import clusterspans


def read(r):
    return clusterspans.span_s(r, "worker", "gang/chip_wait")

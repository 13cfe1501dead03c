"""data_fetch_ms: time inside the program's ``data/fetch`` spans (blocked
on the split coordinator and the object plane) over the traced steps.
data_next_ms less this is the batcher's self time."""

from perfbench import progspans


def read(r):
    return progspans.total_ms_per(r, "data/fetch", "bench/step")

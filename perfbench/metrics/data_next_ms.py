"""data_next_ms: time inside the program's ``data/next`` spans (the batch
iterator, from the consumer's next() to the batch's return) over the
traced steps. A mean: one batch in sixteen carries a block fetch, and a
median would hide it. bench/data less this is the benchmark's own
host-to-device copy."""

from perfbench import progspans


def read(r):
    return progspans.total_ms_per(r, "data/next", "bench/step")

"""scope_mixer_ms: device time of one step inside the operations the program
wrote under ``rt.mixer`` (the token mixers: attention of every kind with its
projections, the gated convolution, the selective and SSD scans, the delta
rule, keye's indexer, selection and KL, the rotary tables, and the residual
sum that closes the branch), forward, recomputed forward and backward, chip
0, median over the traced steps. The class is read from the trace's own
operation names (``perfbench/opscopes.py``), not from shapes. None where the
step holds no such operation (a parent without the scopes, a family without
the class), and where the trace's file cannot be proved to be this run's."""

from perfbench import opscopes


def read(r):
    return opscopes.read_class(r, "mixer")

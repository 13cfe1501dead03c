"""scope_mlp_ms: device time of one step inside the operations the program
wrote under ``rt.mlp`` (the dense feed-forwards: a block's dense
feed-forward with its residual sum, and the prediction module's projection
where a family has one), forward, recomputed forward and backward, chip 0,
median over the traced steps. The class is read from the trace's own
operation names (``perfbench/opscopes.py``), not from shapes. None where the
step holds no such operation (a parent without the scopes, a family without
the class), and where the trace's file cannot be proved to be this run's."""

from perfbench import opscopes


def read(r):
    return opscopes.read_class(r, "mlp")

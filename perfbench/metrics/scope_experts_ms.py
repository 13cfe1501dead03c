"""scope_experts_ms: device time of one step inside the operations the program
wrote under ``rt.experts`` (the routed-expert layers: the router and its
top-k, the pairs' sort, the gathers to the row buffers and back, the grouped
matmuls, the shared expert beside them and the residual sum, inside the
loops' bodies too), forward, recomputed forward and backward, chip 0, median
over the traced steps. The class is read from the trace's own operation
names (``perfbench/opscopes.py``), not from shapes. None where the step
holds no such operation (a parent without the scopes, a family without the
class), and where the trace's file cannot be proved to be this run's."""

from perfbench import opscopes


def read(r):
    return opscopes.read_class(r, "experts")

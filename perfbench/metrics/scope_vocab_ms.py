"""scope_vocab_ms: device time of one step inside the operations the program
wrote under ``rt.vocab`` (what the vocabulary's width costs: the token
embedding's lookup and its gradient's scatter-add, and the loss head's walk
over the chunks (logits, statistics, the two gradient matmuls)), forward,
recomputed forward and backward, chip 0, median over the traced steps. The
class is read from the trace's own operation names
(``perfbench/opscopes.py``), not from shapes. None where the step holds no
such operation (a parent without the scopes, a family without the class),
and where the trace's file cannot be proved to be this run's."""

from perfbench import opscopes


def read(r):
    return opscopes.read_class(r, "vocab")

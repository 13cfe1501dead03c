"""scope_optimizer_ms: device time of one step inside the operations the
program wrote under ``rt.optimizer`` (the optimizer: ``tx.update`` and
``optax.apply_updates`` of ``parallel/train_step.py:build_train_step``, and
under a mesh the gradients' way to their parameters' layout), forward,
recomputed forward and backward, chip 0, median over the traced steps. The
class is read from the trace's own operation names
(``perfbench/opscopes.py``), not from shapes. None where the step holds no
such operation (a parent without the scopes, a family without the class),
and where the trace's file cannot be proved to be this run's."""

from perfbench import opscopes


def read(r):
    return opscopes.read_class(r, "optimizer")

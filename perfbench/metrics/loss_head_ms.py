"""loss_head_ms: device time of one step inside operations of vocabulary
width, chip 0, median over the traced steps. An ``XLA Ops`` event carries
its instruction's HLO text, operands' shapes included, so an operation
counts when any array in it has a dimension equal to the configuration's
``vocab_size``: the loss head (the logits' matmul and its maximum, the
exponential pass, the two gradient matmuls), and with it the token
embedding's lookup, its gradient and its optimizer update (1.6 ms at
GPT-2's width). That is: what the vocabulary's width costs a step. A
``while``, ``conditional`` or ``call`` is left out, as ``xplane.breakdown``
leaves it out: it carries vocabulary-wide operands but its time is that of
the operations inside it, which are events of their own. It means
something only where ``vocab_size`` equals no other dimension of the
program, as in both configurations (50257). None without a traced device
or where no traced step holds such an operation."""

import re
import statistics

from perfbench import xplane


def read(r):
    if not (r.trace and r.trace.ops):
        return None
    wide = re.compile(
        r"\b[a-z]\w*\[(?:\d+,)*%d(?:,\d+)*\]" % r.model["vocab_size"])
    per_step = []
    for _, _, _, ops in xplane.step_device_work(r.trace, 0):
        mine = [(s, e) for name, s, e in ops if wide.search(name)
                and not xplane.short_name(name).startswith(
                    xplane._CONTROL_FLOW)]
        per_step.append(xplane.length(xplane.union(mine)))
    per_step = [ns for ns in per_step if ns]
    return statistics.median(per_step) / 1e6 if per_step else None

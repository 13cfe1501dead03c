"""reducescatter_ms: time per step that reduce-scatters run on chip 0,
median over the traced steps. Under FSDP these are the gradients: each
weight's gradient is summed over the chips and every chip keeps its shard.
The TPU compiler writes a reduce-scatter as a fusion that calls a
computation named ``all-reduce-scatter`` (an all-reduce and a dynamic-slice
inside), which ``xplane.COLLECTIVE`` does not know, so ``collective_ms``
leaves these out; a plain ``%reduce-scatter`` (``-start`` / ``-done``) is
counted too. None where the traced steps hold none: one chip, or a program
that all-reduces its gradients, as the step did before PR 26."""

import re

from perfbench.metrics import allgather_ms

REDUCE_SCATTER = re.compile(
    r"%?reduce-scatter(-start|-done)?\b|.* calls=%all-reduce-scatter\b")


def read(r):
    return allgather_ms.in_flight_ms(r.trace, REDUCE_SCATTER.match)

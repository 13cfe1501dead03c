"""allgather_ms: time per step that all-gathers are in flight on chip 0,
median over the traced steps. Under FSDP these are the weights, gathered
before use in the forward pass, in the recomputation and in the backward
pass. An all-gather is found by its instruction: an ``XLA Ops`` event
carries the HLO text, and the TPU compiler writes an all-gather either as
``%all-gather`` (synchronous, or ``-start`` / ``-done``) or, overlapped
with compute, as a pair ``%async-collective-start`` /
``%async-collective-done``, whose text does not say what it gathers: in the
step programs of this repo every such pair is an all-gather
(tests/test_tpu_compile.py holds the compiled GPT-2 XL step to that). None
where the traced steps hold no such operation: one chip, or a program whose
weights are not sharded."""

import re
import statistics

from perfbench import xplane

ALL_GATHER = re.compile(r"%?(all-gather|async-collective)(-start|-done)?\b")


def in_flight_ms(trace, mine):
    """Median over the traced steps of the time that the operations
    ``mine(name)`` selects are in flight on chip 0 (a ``-start`` to its
    ``-done``, as ``xplane.collective_intervals`` pairs them; any other
    selected operation for its own duration), in ms. None without such an
    operation."""
    if not (trace and trace.ops):
        return None
    per_step = []
    for _, _, _, ops in xplane.step_device_work(trace, 0):
        ops = [op for op in ops if mine(op[0])]
        paired = xplane.collective_intervals(ops)
        alone = [(s, e) for n, s, e in ops if not xplane.is_collective(n)]
        per_step.append(xplane.length(xplane.union(paired + alone)))
    per_step = [ns for ns in per_step if ns]
    return statistics.median(per_step) / 1e6 if per_step else None


def read(r):
    return in_flight_ms(r.trace, ALL_GATHER.match)

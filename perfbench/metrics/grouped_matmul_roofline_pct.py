"""grouped_matmul_roofline_pct: the multiply-adds the held experts' grouped
matmuls need for the rows PRESENT, over the time the kernels took x the
chip's published bf16 peak (perfbench/peaks.json), chip 0, over every
``grouped_matmul_*`` call of the traced steps. The kernels are bound by the
MXU at the cells' shapes (a call reads its rows once a block of columns and
a group's matrix once a visit: bytes O(rows (K + N)) against 2 rows K N
operations), so the peak is their roofline.

The kernels are found as ``grouped_matmul_ms`` finds them. What a call needs
(``needed``) is ``2 x rows present x K x N``: K and N from the call's own
operands, which its event's HLO text carries after the five vectors of the
walk (``rows [R, K]`` and a stack of matrices ``[held, K, N]`` or, read
transposed, ``[held, N, K]``; for a matrix's gradient ``rows [R, K]`` and
``d_out [R, N]``, the result ``[held, K, N]`` giving ``held``), and the rows
present from the program's own count of the step, NOT from the buffer: R is
the row buffer, tokens x experts a token long, of which only the pairs that
fell on held experts are anybody's (a quarter where 16 of 64 experts are
held, and the kernels' grids walk those alone). Counting R would read up to
``published / held`` times the peak. The program reports a step's load where
its loop reads the loss, one ``train/step_aux`` record of the runtime's ring
a step (``mla_moe.held_expert_load``): ``expert_tokens_mean``, the mean rows
a held expert got over the layers, times a call's ``held`` is the mean rows
present a call, and every expert layer makes the same calls, so the sum over
a step's calls is exact. The traced steps are the run's last: their records
are the ring's last, one a traced step, in order. Masked rows of a tile two
groups share and the columns past N in a last block are work a kernel does
beyond that and lower the share.

None where the traced steps hold no kernel, the ring holds fewer
``train/step_aux`` records with a load than steps were traced (a program
that reports none), or the device's peak is unknown.
"""

import re

from perfbench import xplane
from perfbench.metrics.grouped_matmul_ms import KERNEL

_RESULT = re.compile(r" = \(?(.*?)\)? custom-call\(")
_OPERANDS = re.compile(r"custom-call\((.*?)\), custom_call_target=")
_TYPED = re.compile(r"\b([a-z]+\d+)\[([\d,]+)\]")


def _arrays(text):
    """The shapes of the arrays in ``text`` that are no walk's vector."""
    return [tuple(int(n) for n in dims.split(","))
            for dtype, dims in _TYPED.findall(text) if dtype != "s32"]


def shape_of(event_text: str):
    """{"form", "held", "k", "n", "rows_buffered"} of one kernel call, from
    its HLO text; None for a text that is no kernel's or whose operands
    cannot be read."""
    kind = KERNEL.match(event_text)
    operands = _OPERANDS.search(event_text)
    result = _RESULT.search(event_text)
    if not kind or not operands or not result:
        return None
    ins, outs = _arrays(operands.group(1)), _arrays(result.group(1))
    if len(ins) < 2 or not outs or len(ins[0]) != 2:
        return None
    form, (length, k) = kind.group(1), ins[0]
    if form == "matrices":
        if len(ins[1]) != 2 or len(outs[0]) != 3 or ins[1][0] != length:
            return None
        held, n = outs[0][0], ins[1][1]
    else:
        if len(ins[1]) != 3 or k not in ins[1][1:]:
            return None
        held = ins[1][0]
        n = ins[1][1] * ins[1][2] // k
    return {"form": form, "held": held, "k": k, "n": n,
            "rows_buffered": length}


def needed(event_text: str, rows_a_held_expert: float):
    """Operations one kernel call needs at ``rows_a_held_expert`` mean rows
    present a held expert; None as ``shape_of``."""
    call = shape_of(event_text)
    if call is None:
        return None
    return 2.0 * rows_a_held_expert * call["held"] * call["k"] * call["n"]


def step_loads(records, steps: int):
    """``expert_tokens_mean`` of the last ``steps`` ``train/step_aux``
    records of the ring's ``records``, oldest first; None where there are
    fewer."""
    loads = [r["values"]["expert_tokens_mean"] for r in records
             if r["kind"] == "counters" and r["name"] == "train/step_aux"
             and "expert_tokens_mean" in r["values"]]
    return loads[-steps:] if steps and len(loads) >= steps else None


def read(r):
    if not (r.trace and r.trace.ops and r.peaks):
        return None
    from ray_tpu._private import steptrace

    steps = [ops for _, _, _, ops in xplane.step_device_work(r.trace, 0)]

    loads = step_loads(steptrace.process_snapshot()["records"], len(steps))
    if loads is None:
        return None
    flops, spent = 0.0, 0
    for ops, load in zip(steps, loads):
        for name, start, end in ops:
            call = needed(name, load)
            if call:
                flops, spent = flops + call, spent + (end - start)
    if not spent:
        return None
    return 100.0 * flops / (spent / 1e9 * r.peaks["bf16_flops_per_s"])

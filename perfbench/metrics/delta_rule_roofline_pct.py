"""delta_rule_roofline_pct: the least time the chip could take over the gated
delta rule's calls, over the time they took: chip 0, over every
``gated_delta_fwd`` / ``gated_delta_bwd`` call of the traced steps. The least
time of a call is the larger of its bytes over the chip's published HBM
bandwidth and its operations over the published bf16 peak
(perfbench/peaks.json).

The kernels are found as ``delta_rule_ms`` finds them. What a call needs is
counted from the rule's own sizes (``needed``): tokens, value heads H, key
heads, d_k, d_v and the operands' dtypes, which the call's event text
carries in its operands' shapes: ``q`` and ``k`` [B, T, key heads x d_k]
lead both kernels' operands, ``v`` [B, T, H x d_v] follows, then the decay
and ``beta`` [B, H, ...] float32; the boundary states [B, H, kept, d_k, d_v]
float32 close the forward's results and the backward's operands and give
d_k. Neither the chunk nor the form enters: the count is the recurrence's,
one position a step, so that another implementation of the same rule reads
against the same work.

- Forward: ``q``, ``k``, ``v``, ``g``, ``beta`` in and ``o`` out; 7
  operations a position, value head and entry of the [d_k, d_v] state
  (``exp(g) S``: 1; ``S'^T k``: 2; the outer product and its sum: 2; ``S^T
  q``: 2).
- Backward: those operands and ``o``'s cotangent in, the five gradients
  out; 15 operations an entry (``q do^T`` into the state's gradient and ``S
  do``: 4; through the outer product: 4; through ``S'^T k``: 4; through the
  decay and into ``g``: 3). The states made again from the boundaries are
  recomputation and do not count, nor do the boundaries' bytes.

At the published sizes (32 heads on 16, 128 x 128, bfloat16) the bytes bound
both passes: forward 24,832 bytes a token against 3.67 M operations (30.3 ns
against 18.6), backward 41,472 against 7.86 M (50.6 ns against 39.9). A
chunked form spends more operations than the recurrence (the chunk's
triangular solve and its C x C products) and most of them on the MXU in the
operands' dtype, part in float32: the share reads how far above the floor of
the rule itself the kernels run.

None where the traced steps hold no kernel or the device's peaks are unknown.
"""

import re

from perfbench import xplane
from perfbench.metrics.delta_rule_ms import KERNEL

FWD_OPS, BWD_OPS = 7, 15
_RESULTS = re.compile(r" = (.*?) custom-call\(")
_OPERANDS = re.compile(r"custom-call\((.*?)\), custom_call_target=")
_TYPED = re.compile(r"\b([a-z]+\d+)\[([\d,]+)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def _typed(text):
    return [(dtype, tuple(int(n) for n in dims.split(",")))
            for dtype, dims in _TYPED.findall(text)]


def needed(event_text: str):
    """{"bytes", "flops"} one kernel call needs, from its HLO text; None
    for a text that is no kernel's or whose operands cannot be read."""
    kind = KERNEL.match(event_text)
    operands = _OPERANDS.search(event_text)
    results = _RESULTS.search(event_text)
    if not kind or not operands or not results:
        return None
    ins, outs = _typed(operands.group(1)), _typed(results.group(1))
    if len(ins) < 5 or not outs or any(t not in _BYTES for t, _ in ins + outs):
        return None
    (q_type, q_shape), (v_type, v_shape), (_, gate_shape) = (
        ins[0], ins[2], ins[3])
    backward = kind.group(1) == "bwd"
    bounds = (ins if backward else outs)[-1][1]
    if (len(q_shape) != 3 or len(v_shape) != 3 or len(gate_shape) < 2
            or len(bounds) != 5 or q_shape[:2] != v_shape[:2]):
        return None
    tokens, heads = q_shape[0] * q_shape[1], gate_shape[1]
    d_k, d_v = bounds[-2:]
    if bounds[1] != heads or v_shape[2] != heads * d_v or q_shape[2] % d_k:
        return None
    qk = 2 * tokens * q_shape[2] * _BYTES[q_type]
    vo = tokens * v_shape[2] * _BYTES[v_type]
    gates = 2 * tokens * heads * 4
    entries = tokens * heads * d_k * d_v
    if backward:
        return {"bytes": 2 * qk + 3 * vo + 2 * gates,
                "flops": BWD_OPS * entries}
    return {"bytes": qk + 2 * vo + gates, "flops": FWD_OPS * entries}


def read(r):
    if not (r.trace and r.trace.ops and r.peaks):
        return None
    least, spent = 0.0, 0
    for _, _, _, ops in xplane.step_device_work(r.trace, 0):
        for name, start, end in ops:
            call = needed(name)
            if call:
                least += max(call["bytes"] / r.peaks["hbm_bytes_per_s"],
                             call["flops"] / r.peaks["bf16_flops_per_s"])
                spent += end - start
    if not spent:
        return None
    return 100.0 * least / (spent / 1e9)

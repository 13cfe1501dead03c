"""ssd_roofline_pct: the least time the chip could take over the scalar-decay
scan's calls, over the time they took: chip 0, over every ``ssd_fwd`` /
``ssd_bwd`` call of the traced steps. The least time of a call is the larger
of its bytes over the chip's published HBM bandwidth and its operations over
the published bf16 peak (perfbench/peaks.json).

The kernels are found as ``ssd_ms`` finds them. What a call needs is counted
from the recurrence's own sizes (``needed``): tokens, heads H of head_dim P,
groups G of N states and the operands' dtypes, which the call's event text
carries in its operands' shapes: ``x`` [B, T, H x P] leads both kernels'
operands, ``B`` and ``C`` [B, T, G x N] follow, then the summed log-decay and
``dt`` [B, H, ...] float32; the boundary states [B, kept, H / 2, 2 P, N]
float32 close the forward's results and the backward's operands and give N.
Neither the chunk nor the form enters: the count is the recurrence's, one
position a step, so that another implementation of the same scan reads
against the same work.

- Forward: ``x``, ``B``, ``C``, ``dt`` in and ``y`` out; 5 operations a
  position, head and entry of the [P, N] state (``exp(dt A) S``: 1; the
  outer product ``(dt x) B^T`` and its sum into the state: 2; ``S C``: 2).
- Backward: those operands and ``y``'s cotangent in, the four gradients
  out; 11 operations an entry (through ``S C``: ``dy C^T`` into the state's
  gradient and ``S^T dy`` into ``dC``: 4; through the outer product: ``dS
  B`` into ``d(dt x)`` and ``(dt x)^T dS`` into ``dB``: 4; through the
  decay: the state's gradient decayed, 1, and ``sum(S o dS)`` into the
  decay's: 2). The states made again from the boundaries are recomputation
  and do not count, nor do the boundaries' bytes, nor ``D x``.

At the published sizes (64 heads of 64, 8 groups of 128 states, bfloat16)
the bytes bound both passes: forward 20,736 bytes a token against 2.62 M
operations (25.3 ns against 13.3), backward 33,280 against 5.77 M (40.6 ns
against 29.3). A chunked form spends more operations than the recurrence
(the chunk's C x C products, made a head each) and part of them in float32
on the vector unit: the share reads how far above the floor of the scan
itself the kernels run.

None where the traced steps hold no kernel or the device's peaks are unknown.
"""

import re

from perfbench import xplane
from perfbench.metrics.ssd_ms import KERNEL

FWD_OPS, BWD_OPS = 5, 11
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
    (x_type, x_shape), (b_type, b_shape), (_, gate_shape) = (
        ins[0], ins[1], ins[3])
    backward = kind.group(1) == "bwd"
    bounds = (ins if backward else outs)[-1][1]
    if (len(x_shape) != 3 or len(b_shape) != 3 or len(gate_shape) < 2
            or len(bounds) != 5 or x_shape[:2] != b_shape[:2]):
        return None
    tokens, heads = x_shape[0] * x_shape[1], gate_shape[1]
    states = bounds[-1]
    if (x_shape[2] % heads or bounds[2] * bounds[3] != x_shape[2]
            or b_shape[2] % states):
        return None
    xs = tokens * x_shape[2] * _BYTES[x_type]
    bc = 2 * tokens * b_shape[2] * _BYTES[b_type]
    dts = tokens * heads * 4
    entries = tokens * x_shape[2] * states
    if backward:
        return {"bytes": 3 * xs + 2 * bc + 2 * dts,
                "flops": BWD_OPS * entries}
    return {"bytes": 2 * xs + bc + dts, "flops": FWD_OPS * entries}


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

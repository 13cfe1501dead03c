"""attn_kernel_roofline_pct: the matmul operations the flash-attention
kernels' algorithm needs, over the time the kernels took x the chip's
published bf16 peak (perfbench/peaks.json), chip 0, over the traced steps.
The kernels are bound by the MXU (their bytes are O(T d) against O(T^2 d)
operations), so the peak is their roofline.

The kernels are found as ``attn_kernel_ms`` finds them (a
``tpu_custom_call`` named ``flash_fwd*`` / ``flash_bwd*``). What a call
needs is counted from its own operands, which its event's HLO text carries
(``needed_flops``): q [B, T, d_qk], k [B, S, d_qk] and the values' width
d_v. Every call in this repo is causal self-attention (T = S), so query i
meets keys 0..i: T (T + 1) / 2 pairs a head, the mask discounted exactly.
A pair costs the forward kernel 2 d_qk (q . k) + 2 d_v (p v) operations and
the backward kernel 2 (3 d_qk + 2 d_v): the scores again, dV = P^T dO,
dP = dO V^T, dK = dS^T Q, dQ = dS K. A forward call that a step repeats to
save memory counts with its own time: each call is held to what it needs.
Work the kernel does beyond that (the dead half of a diagonal tile, lanes
a 192-wide key is padded to) is not needed and lowers the share. A call
that were not causal would need twice as many: the reader would then read
half its true share, never more than it.

None where the traced steps hold no kernel or the device's peak is unknown.
"""

import re

from perfbench import xplane
from perfbench.metrics.attn_kernel_ms import KERNEL

_OPERANDS = re.compile(r"custom-call\((.*?)\), custom_call_target=")
_SHAPE = re.compile(r"\b[a-z]\w*\[([\d,]+)\]")


def needed_flops(event_text: str):
    """Operations one kernel call needs, from its HLO text; None for a text
    that is no kernel's or whose operands cannot be read."""
    kind = KERNEL.match(event_text)
    operands = _OPERANDS.search(event_text)
    if not kind or not operands:
        return None
    shapes = [tuple(int(n) for n in dims.split(","))
              for dims in _SHAPE.findall(operands.group(1))]
    if len(shapes) < 3 or any(len(s) != 3 for s in shapes[:3]):
        return None
    (b, t, d_qk), (_, s, _), third = shapes[:3]
    # forward: V^T [B, d_v, S]; backward: V [B, S, d_v]
    forward = kind.group(1) == "fwd"
    d_v = third[1] if forward else third[2]
    pairs = b * (t * (t + 1) // 2 + t * (s - t))   # causal, the last t of s
    per_pair = (2 * (d_qk + d_v) if forward else 2 * (3 * d_qk + 2 * d_v))
    return pairs * per_pair


def read(r):
    if not (r.trace and r.trace.ops and r.peaks):
        return None
    needed, spent = 0, 0
    for _, _, _, ops in xplane.step_device_work(r.trace, 0):
        for name, start, end in ops:
            flops = needed_flops(name)
            if flops:
                needed, spent = needed + flops, spent + (end - start)
    if not spent:
        return None
    return 100.0 * needed / (spent / 1e9 * r.peaks["bf16_flops_per_s"])

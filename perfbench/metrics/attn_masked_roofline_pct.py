"""attn_masked_roofline_pct: the matmul operations that each flash-attention
call's own mask leaves, over the time the calls took x the chip's published
bf16 peak (perfbench/peaks.json), chip 0, over every flash call of the
traced steps, windowed or not. The kernels are bound by the MXU (their bytes
are O(T d) against O(T W d) operations), so the peak is their roofline.

The kernels are found as ``attn_kernel_ms`` finds them. What a call needs is
counted from its own instruction (``needed_flops``): the query heads B and
the length T from q's operand [B, T, d_qk] (keys and values may have fewer
heads: each query head still meets its own pairs), the keys' length S and
the values' width d_v from the operands after it, and the window W from the
call's name (``flash_fwd_w<W>``; none: causal). Every call in this repo is
causal self-attention: query i sees the keys up to its own position, and
under a window the last W of them, its own among them: ``W T - W (W - 1) /
2`` pairs a head where W < T, else ``T (T + 1) / 2``. A pair costs the
forward kernel 2 d_qk (q . k) + 2 d_v (p v) operations and the backward
kernel 2 (3 d_qk + 2 d_v): the scores again, dV = P^T dO, dP = dO V^T,
dK = dS^T Q, dQ = dS K. Work a kernel does beyond that (the masked half of a
diagonal or a trailing tile) is not needed and lowers the share.
``attn_kernel_roofline_pct`` keeps its own count, which knows no window.

None where the traced steps hold no kernel or the device's peak is unknown.
"""

from perfbench import xplane
from perfbench.metrics.attn_kernel_ms import KERNEL
from perfbench.metrics.attn_kernel_roofline_pct import _OPERANDS, _SHAPE
from perfbench.metrics.attn_window_ms import WINDOWED


def attended_pairs(t: int, s: int, window=None) -> int:
    """Query-key pairs a head of causal attention of ``t`` queries, the
    last ``t`` of ``s`` positions, each seeing the last ``window`` keys up
    to its own (all of them without one)."""
    pairs = t * (t + 1) // 2 + t * (s - t)
    if window is None or window >= s:
        return pairs
    # the first queries see fewer than a window's keys
    short = max(0, min(t, window - 1 - (s - t)))
    first = s - t + 1      # keys the first query sees without a window
    return (t - short) * window + short * (2 * first + short - 1) // 2


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
    # forward: V^T [B_kv, d_v, S]; backward: V [B_kv, S, d_v]
    forward = kind.group(1) == "fwd"
    d_v = third[1] if forward else third[2]
    window = WINDOWED.match(event_text)
    pairs = b * attended_pairs(t, s, int(window.group(2)) if window else None)
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

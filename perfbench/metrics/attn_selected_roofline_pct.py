"""attn_selected_roofline_pct: the matmul operations that a selection
leaves each flash-attention call under it, over the time those calls took x
the chip's published bf16 peak (perfbench/peaks.json), chip 0, over every
such call of the traced steps. The kernels are bound by the MXU, so the peak
is their roofline.

The calls are found as ``attn_selected_ms`` finds them. What a call needs
is counted from its own instruction (``needed_flops``): the query heads B
and the positions T from q's operand [B, T, d_qk], the values' width d_v
from the third operand, the batch rows from the mask's operand [rows, T,
T], and the keys a query K from the call's name (``flash_fwd_sel<K>``). A
query at position t sees ``min(t + 1, K)`` keys (``selected_pairs``: every
earlier key while there are no more than K, then K), so a head has ``K (K +
1) / 2 + (T - K) K`` pairs of a causal mask's ``T (T + 1) / 2``. A pair
costs the forward kernel 2 d_qk (q . k) + 2 d_v (p v) operations and the
backward kernel 2 (3 d_qk + 2 d_v), as ``attn_kernel_roofline_pct`` counts
them. The kernels walk every tile up to the diagonal, since the selected
keys lie anywhere: the work beyond the selected pairs is not needed and
lowers the share (at 2,048 of 16,384 to under a quarter of what the same
kernels reach under the plain causal mask).

None where the traced steps hold no such call or the device's peak is
unknown.
"""

from perfbench import xplane
from perfbench.metrics.attn_kernel_roofline_pct import _OPERANDS, _SHAPE
from perfbench.metrics.attn_selected_ms import SELECTED


def selected_pairs(length: int, keys: int) -> int:
    """Query-key pairs a head that a selection of ``keys`` leaves of one
    sequence of ``length`` positions."""
    short = min(length, keys)
    return short * (short + 1) // 2 + (length - short) * keys


def needed_flops(event_text: str):
    """Operations one kernel call under a selection needs, from its HLO
    text; None for a text that is no such call's or whose operands cannot be
    read."""
    kind = SELECTED.match(event_text)
    operands = _OPERANDS.search(event_text)
    if not kind or not operands:
        return None
    shapes = [tuple(int(n) for n in dims.split(","))
              for dims in _SHAPE.findall(operands.group(1))]
    if len(shapes) < 3 or any(len(s) != 3 for s in shapes[:3]):
        return None
    (b, t, d_qk), _, third = shapes[:3]
    # forward: V^T [B_kv, d_v, T]; backward: V [B_kv, T, d_v]
    forward = kind.group(1) == "fwd"
    d_v = third[1] if forward else third[2]
    pairs = b * selected_pairs(t, int(kind.group(2)))
    per_pair = (2 * (d_qk + d_v) if forward else 2 * (3 * d_qk + 2 * d_v))
    return pairs * per_pair


def roofline_pct(r, needed_of):
    """100 x the operations ``needed_of(event text)`` finds, over the time
    of the operations it finds them in x the bf16 peak; None where it finds
    none."""
    if not (r.trace and r.trace.ops and r.peaks):
        return None
    needed, spent = 0, 0
    for _, _, _, ops in xplane.step_device_work(r.trace, 0):
        for name, start, end in ops:
            flops = needed_of(name)
            if flops:
                needed, spent = needed + flops, spent + (end - start)
    if not spent:
        return None
    return 100.0 * needed / (spent / 1e9 * r.peaks["bf16_flops_per_s"])


def read(r):
    return roofline_pct(r, needed_flops)

"""index_select_roofline_pct: the matmul operations the index scores need,
over the time the selection's kernel calls took x the chip's published bf16
peak (perfbench/peaks.json), chip 0, over every such call of the traced
steps. The calls are found as ``index_select_ms`` finds them. What a call
needs is counted from its own instruction (``needed_flops``): the index
keys' operand [B, T, W] is the first, the index queries' [B, J, W, T] the
second; every query meets every earlier key once an index head, ``2 J W``
operations a pair over ``B T (T + 1) / 2`` pairs. The threshold search (32
counts over a query's scores) and the mask's write are compares and stores,
not matmul operations: they are the kernel's time and none of its needed
operations, so the share says how far the kernel is from a pure scores
kernel at the MXU's peak, and a score head of 64 fills half the MXU's depth
at best. None where the traced steps hold no such call or the device's peak
is unknown."""

from perfbench.metrics.attn_kernel_roofline_pct import _OPERANDS, _SHAPE
from perfbench.metrics.attn_selected_roofline_pct import roofline_pct
from perfbench.metrics.index_select_ms import SELECT


def needed_flops(event_text: str):
    """Operations one selection call's scores need, from its HLO text; None
    for a text that is no such call's or whose operands cannot be read."""
    operands = _OPERANDS.search(event_text)
    if not SELECT.match(event_text) or not operands:
        return None
    shapes = [tuple(int(n) for n in dims.split(","))
              for dims in _SHAPE.findall(operands.group(1))]
    if len(shapes) < 2 or len(shapes[0]) != 3 or len(shapes[1]) != 4:
        return None
    (b, t, width), (_, heads, _, _) = shapes[:2]
    return b * (t * (t + 1) // 2) * 2 * heads * width


def read(r):
    return roofline_pct(r, needed_flops)

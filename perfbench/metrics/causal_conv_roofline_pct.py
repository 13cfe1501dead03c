"""causal_conv_roofline_pct: the least time the chip could take over the
plain causal convolution's calls, over the time they took: chip 0, over
every ``causal_conv_fwd`` / ``causal_conv_bwd`` call of the traced steps. The
least time of a call is the larger of its bytes over the chip's published
HBM bandwidth and its operations over the published bf16 peak
(perfbench/peaks.json).

The kernels are found as ``causal_conv_ms`` finds them. What a call needs is
counted from its own operands, which its event's HLO text carries
(``needed``): ``x`` [B, T, channels] in its dtype leads both kernels'
operands (it is handed over again for the rows before a block: it counts
once), the taps [K, channels] float32 close them, with a bias one row more.
The bytes are ``ray_tpu.ops.conv.causal_needed_bytes``'s, counted here from
the text so that the reader imports nothing of the program:

- Forward: ``x`` and the taps in, ``y`` out: 2 numbers a position and
  channel; 2 K + 4 operations (K products, K - 1 sums, the bias, the SiLU's
  four).
- Backward: ``x``, ``dy`` and the taps in, ``dx`` and the taps' float32
  gradient out: 3 numbers a position and channel; 4 K + 9 operations (the
  sum again: 2 K; the SiLU's slope and ``ds``: 8; K products and K - 1 sums
  for ``dx``; a product and a sum a tap and one sum for the bias's
  gradient, less what the bias's absence saves: counted with it).

The bytes bound, by a wide margin (in bfloat16 4 bytes against 12
operations a position and channel forward: 4.9 ps against 0.06). The element
work runs on the vector unit, for which the chip publishes no peak, so the
share reads how far above its memory floor a kernel runs.

None where the traced steps hold no kernel or the device's peaks are unknown.
"""

import re

from perfbench import xplane
from perfbench.metrics.causal_conv_ms import KERNEL

_OPERANDS = re.compile(r"custom-call\((.*?)\), custom_call_target=")
_TYPED = re.compile(r"\b([a-z]+\d+)\[([\d,]+)\]")
_BYTES = {"bf16": 2, "f16": 2, "f32": 4}


def needed(event_text: str):
    """{"bytes", "flops"} one kernel call needs, from its HLO text; None
    for a text that is no kernel's or whose operands cannot be read."""
    kind = KERNEL.match(event_text)
    operands = _OPERANDS.search(event_text)
    if not kind or not operands:
        return None
    typed = [(dtype, tuple(int(n) for n in dims.split(",")))
             for dtype, dims in _TYPED.findall(operands.group(1))]
    if len(typed) < 2 or any(t not in _BYTES for t, _ in typed):
        return None
    (x_type, x_shape), (taps_type, taps_shape) = typed[0], typed[-1]
    if (len(x_shape) != 3 or len(taps_shape) != 2
            or x_shape[2] != taps_shape[1]):
        return None
    cells = x_shape[0] * x_shape[1] * x_shape[2]
    rows = taps_shape[0]                     # the taps, and a bias if any
    weights = rows * taps_shape[1] * _BYTES[taps_type]
    if kind.group(1) == "fwd":
        return {"bytes": 2 * cells * _BYTES[x_type] + weights,
                "flops": (2 * rows + 2) * cells}
    return {"bytes": 3 * cells * _BYTES[x_type] + 2 * weights,
            "flops": (4 * rows + 5) * cells}


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

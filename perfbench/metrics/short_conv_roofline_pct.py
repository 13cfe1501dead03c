"""short_conv_roofline_pct: the least time the chip could take over the
gated short convolution's calls, over the time they took: chip 0, over every
``short_conv_fwd`` / ``short_conv_bwd`` call of the traced steps. The least
time of a call is the larger of its bytes over the chip's published HBM
bandwidth and its operations over the published bf16 peak
(perfbench/peaks.json).

The kernels are found as ``short_conv_ms`` finds them. What a call needs is
counted from its own operands, which its event's HLO text carries
(``needed``): ``bcx`` [B, T, 3 h] in its dtype leads both kernels' operands
(it is handed over several times, once a block the kernel reads of it: it
counts once), the taps [K, h] float32 close them.

- Forward: ``bcx`` and the taps in, ``y`` [B, T, h] out: 4 numbers a
  position and channel; 7 operations (``B x``, three products with the taps
  and their two sums, ``C c``).
- Backward: ``bcx``, ``dy`` and the taps in, ``dbcx`` and the taps' float32
  gradient out: 7 numbers a position and channel; 21 operations (``z`` and
  ``c`` again: 6; ``dC``, ``dc``; three products and two sums for ``dz``;
  ``dB``, ``dx``; three products and three sums for the taps' gradient).

The bytes bound, by a wide margin (in bfloat16 8 bytes against 7 operations
a position and channel forward: 9.8 ps against 0.04). The element work runs
on the vector unit, for which the chip publishes no peak, so the share reads
how far above its memory floor a kernel runs, not how well it uses the unit
that limits it.

None where the traced steps hold no kernel or the device's peaks are unknown.
"""

import re

from perfbench import xplane
from perfbench.metrics.short_conv_ms import KERNEL

FWD_OPS, BWD_OPS = 7, 21
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
    (bcx_type, bcx_shape), (taps_type, taps_shape) = typed[0], typed[-1]
    if (len(bcx_shape) != 3 or len(taps_shape) != 2
            or bcx_shape[2] != 3 * taps_shape[1]):
        return None
    cells = bcx_shape[0] * bcx_shape[1] * taps_shape[1]
    taps = taps_shape[0] * taps_shape[1] * _BYTES[taps_type]
    if kind.group(1) == "fwd":
        return {"bytes": 4 * cells * _BYTES[bcx_type] + taps,
                "flops": FWD_OPS * cells}
    return {"bytes": 7 * cells * _BYTES[bcx_type] + 2 * taps,
            "flops": BWD_OPS * cells}


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

"""ssm_scan_roofline_pct: the least time the chip could take over the
selective scan's calls, over the time they took: chip 0, over every
``ssm_scan_fwd`` / ``ssm_scan_bwd`` call of the traced steps. The least time
of a call is the larger of its bytes over the chip's published HBM bandwidth
and its operations over the published bf16 peak (perfbench/peaks.json).

The kernels are found as ``ssm_scan_ms`` finds them. What a call needs is
counted from its own operands, which its event's HLO text carries
(``needed``): ``x`` [B, T, channels] in its dtype and ``delta`` [B, T,
channels] float32 lead both kernels' operands, ``A^T`` [states, channels]
follows; ``B`` and ``C`` are needed as [B, T, states] in their dtype (the
kernels are handed them spread over a lane tile, 128 times the bytes: what
a kernel moves beyond what the recurrence needs lowers the share, as a
masked tile does the flash kernels').

- Forward: ``x``, ``delta``, ``B``, ``C``, ``A``, ``D`` in and ``y`` out;
  7 operations a position, channel and state (``delta A``, its exponential
  counted as one, ``decay h``, ``(delta x) B``, their sum, ``h C`` and its
  sum over the states).
- Backward: those operands, ``dy`` and the boundary states [B, T / chunk,
  states, channels] float32 in; ``dx``, ``ddelta`` (float32), ``dB``,
  ``dC`` and ``dA`` out; 20 operations: the chunk's states again (5) and 15
  for the six gradients and the state's.

The bytes bound, by a wide margin (at 16,384 x 5,120 x 16: 0.67 GB against
9.4 G operations forward, 0.82 ms against 0.05). The scan's element work
runs on the vector unit, for which the chip publishes no peak, so the share
reads how far above its memory floor the kernels run, not how well they use
the unit that limits them.

None where the traced steps hold no kernel or the device's peaks are unknown.
"""

import math
import re

from perfbench import xplane
from perfbench.metrics.ssm_scan_ms import KERNEL

FWD_OPS, BWD_OPS = 7, 20
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
    if len(typed) < 6 or any(t not in _BYTES for t, _ in typed):
        return None
    (x_type, x_shape), (d_type, _), (_, a_shape), (b_type, _) = typed[:4]
    if len(x_shape) != 3 or len(a_shape) != 2:
        return None
    positions = x_shape[0] * x_shape[1]
    states, channels = a_shape
    rows = positions * channels
    x_bytes, narrow = rows * _BYTES[x_type], positions * states * _BYTES[b_type]
    small = (states + 1) * channels * 4                      # A and D
    moved = x_bytes + rows * _BYTES[d_type] + 2 * narrow + small + x_bytes
    if kind.group(1) == "fwd":
        return {"bytes": moved, "flops": FWD_OPS * rows * states}
    bounds = math.prod(typed[-1][1]) * _BYTES[typed[-1][0]]
    # dy and the boundaries in; ddelta (float32) and dA out, dx where y was
    moved += x_bytes + bounds + rows * 4 + 2 * narrow + states * channels * 4
    return {"bytes": moved, "flops": BWD_OPS * rows * states}


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

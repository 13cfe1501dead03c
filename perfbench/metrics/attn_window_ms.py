"""attn_window_ms: device time of one step inside the flash-attention
kernels' windowed calls (``ray_tpu/ops/attention.py``), chip 0, median over
the traced steps: the share of ``attn_kernel_ms`` that a model's window
layers take. A windowed call is found as ``attn_kernel_ms`` finds a kernel,
and told from a call without a window by its instruction's name: the
``pallas_call`` of a call under a window of W keys is named
``flash_fwd_w<W>`` / ``flash_bwd_w<W>`` (that name is part of this
yardstick). None where the traced steps hold no such operation: a model
without window layers, a program without the window, the XLA attention
path, a CPU."""

import re
import statistics

from perfbench import xplane

WINDOWED = re.compile(
    r'%?flash_(fwd|bwd)_w(\d+)[\w.\-]* = .*custom_call_target="tpu_custom_call"')


def read(r):
    if not (r.trace and r.trace.ops):
        return None
    per_step = [sum(e - s for name, s, e in ops if WINDOWED.match(name))
                for _, _, _, ops in xplane.step_device_work(r.trace, 0)]
    per_step = [ns for ns in per_step if ns]
    return statistics.median(per_step) / 1e6 if per_step else None

"""causal_conv_ms: device time of one step inside the plain causal
convolution's kernels (``ray_tpu/ops/conv.py:causal_conv``), chip 0, median
over the traced steps. A kernel is found as ``attn_kernel_ms`` finds one: an
``XLA Ops`` event carries the HLO text, and a Pallas call is a
``tpu_custom_call`` whose instruction is named after the kernel
(``causal_conv_fwd``, ``causal_conv_bwd``: the ``name`` its ``pallas_call``
gives is part of this yardstick; the gated pair's ``short_conv_*`` do not
match). None where the traced steps hold no such operation: a model without
such a convolution, a program whose convolution is XLA's, a CPU."""

import re
import statistics

from perfbench import xplane

KERNEL = re.compile(
    r'%?causal_conv_(fwd|bwd)[\w.\-]* = .*custom_call_target="tpu_custom_call"')


def read(r):
    if not (r.trace and r.trace.ops):
        return None
    per_step = [sum(e - s for name, s, e in ops if KERNEL.match(name))
                for _, _, _, ops in xplane.step_device_work(r.trace, 0)]
    per_step = [ns for ns in per_step if ns]
    return statistics.median(per_step) / 1e6 if per_step else None

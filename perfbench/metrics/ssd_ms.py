"""ssd_ms: device time of one step inside the scalar-decay state-space
scan's kernels (``ray_tpu/ops/ssm.py:ssd_scan``), chip 0, median over the
traced steps. A kernel is found as ``attn_kernel_ms`` finds one: an ``XLA
Ops`` event carries the HLO text, and a Pallas call is a ``tpu_custom_call``
whose instruction is named after the kernel (``ssd_fwd``, ``ssd_bwd``: the
``name`` its ``pallas_call`` gives is part of this yardstick; the selective
scan's ``ssm_scan_*`` do not match). None where the traced steps hold no
such operation: a model without such mixers, a program without the kernels
(the chunked twin, the parent of PR 58), a CPU."""

import re
import statistics

from perfbench import xplane

KERNEL = re.compile(
    r'%?ssd_(fwd|bwd)[\w.\-]* = .*custom_call_target="tpu_custom_call"')


def read(r):
    if not (r.trace and r.trace.ops):
        return None
    per_step = [sum(e - s for name, s, e in ops if KERNEL.match(name))
                for _, _, _, ops in xplane.step_device_work(r.trace, 0)]
    per_step = [ns for ns in per_step if ns]
    return statistics.median(per_step) / 1e6 if per_step else None

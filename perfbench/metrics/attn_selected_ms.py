"""attn_selected_ms: device time of one step inside the flash-attention
kernels' calls under a selection (``ray_tpu/ops/attention.py``:
``normed_rotary_self_attention(..., selected=)``: every query sees the keys
a mask the step computed says), chip 0, median over the traced steps. Such a
call is found as ``attn_kernel_ms`` finds a kernel, and told from a call
under another mask by its instruction's name: the ``pallas_call`` of a call
under a selection of K keys a query is named ``flash_fwd_sel<K>`` /
``flash_bwd_sel<K>`` (that name is part of this yardstick). None where the
traced steps hold no such operation: a model without a selection, a program
without the mask's operand, the dense mask in ``jnp``, a CPU."""

import re
import statistics

from perfbench import xplane

SELECTED = re.compile(
    r'%?flash_(fwd|bwd)_sel(\d+)[\w.\-]* = .*custom_call_target="tpu_custom_call"')


def kernel_ms(trace, found):
    """Median over the traced steps of chip 0's time in the operations whose
    event text ``found`` matches, in ms; None where no step holds one."""
    per_step = [sum(e - s for name, s, e in ops if found.match(name))
                for _, _, _, ops in xplane.step_device_work(trace, 0)]
    per_step = [ns for ns in per_step if ns]
    return statistics.median(per_step) / 1e6 if per_step else None


def read(r):
    if not (r.trace and r.trace.ops):
        return None
    return kernel_ms(r.trace, SELECTED)

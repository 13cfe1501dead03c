"""attn_blockdiff_ms: device time of one step inside the flash-attention
kernels' calls under the block-diffusion mask (``ray_tpu/ops/attention.py``:
``seen_by_block``), chip 0, median over the traced steps. Such a call is
found as ``attn_kernel_ms`` finds a kernel, and told from a call under
another mask by its instruction's name: the ``pallas_call`` of a call over
two streams in blocks of D tokens is named ``flash_fwd_bd<D>`` /
``flash_bwd_bd<D>`` (that name is part of this yardstick). None where the
traced steps hold no such operation: a model of another objective, a
program without the mask, the dense mask in ``jnp``, a CPU."""

import re
import statistics

from perfbench import xplane

BY_BLOCK = re.compile(
    r'%?flash_(fwd|bwd)_bd(\d+)[\w.\-]* = .*custom_call_target="tpu_custom_call"')


def read(r):
    if not (r.trace and r.trace.ops):
        return None
    per_step = [sum(e - s for name, s, e in ops if BY_BLOCK.match(name))
                for _, _, _, ops in xplane.step_device_work(r.trace, 0)]
    per_step = [ns for ns in per_step if ns]
    return statistics.median(per_step) / 1e6 if per_step else None

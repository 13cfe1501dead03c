"""index_select_ms: device time of one step inside the indexer's selection
(``ray_tpu/ops/sparse_index.py:select``): the index scores of every query
against every earlier key, each query's exact threshold (the K-th largest
of its row) and the mask's write, chip 0, median over the traced steps. The
three are one Pallas kernel, found by its instruction's name as
``attn_kernel_ms`` finds a kernel: ``index_select_top<K>`` (that name is
part of this yardstick). With recomputation the kernel runs twice a layer a
step, and both runs count. None where the traced steps hold no such
operation: a model without an indexer, the ``jnp`` twin, a CPU."""

import re

from perfbench.metrics.attn_selected_ms import kernel_ms

SELECT = re.compile(
    r'%?index_select_top(\d+)[\w.\-]* = .*custom_call_target="tpu_custom_call"')


def read(r):
    if not (r.trace and r.trace.ops):
        return None
    return kernel_ms(r.trace, SELECT)

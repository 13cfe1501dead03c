"""index_loss_ms: device time of one step inside the indexer's loss
(``ray_tpu/ops/sparse_index.py:index_kl``): every query head's scores
against its keys again, their probabilities summed over the heads, the index
scores again, the KL's part and the three gradients' parts of every tile up
to the diagonal, chip 0, median over the traced steps. One Pallas kernel,
found by its instruction's name as ``attn_kernel_ms`` finds a kernel:
``index_kl`` (that name is part of this yardstick). Its forward rule keeps
the gradients, so it runs once a layer a step, recomputation or not. None
where the traced steps hold no such operation: a model without an indexer,
the ``jnp`` twin, a CPU."""

import re

from perfbench.metrics.attn_selected_ms import kernel_ms

LOSS = re.compile(
    r'%?index_kl[\w.\-]* = .*custom_call_target="tpu_custom_call"')


def read(r):
    if not (r.trace and r.trace.ops):
        return None
    return kernel_ms(r.trace, LOSS)

"""grouped_matmul_ms: device time of one step inside the held experts'
grouped-matmul kernels (``ray_tpu/ops/grouped_matmul.py``: rows by their
group's matrix, the same against the matrix transposed, and a group's rows
against its own into the matrix's gradient), chip 0, median over the traced
steps. A kernel is found as ``attn_kernel_ms`` finds one: an ``XLA Ops``
event carries the HLO text, and a Pallas call is a ``tpu_custom_call`` whose
instruction is named after the kernel (``grouped_matmul_rows``,
``grouped_matmul_rows_t``, ``grouped_matmul_matrices``: the ``name`` its
``pallas_call`` gives is part of this yardstick). None where the traced
steps hold no such operation: a model without held experts, a program
without the kernels (the compiler's ``ragged-dot``: the parent of PR 61, a
mesh, a shape the tiles refuse), a CPU."""

import re
import statistics

from perfbench import xplane

KERNEL = re.compile(
    r"%?grouped_matmul_(rows_t|rows|matrices)(?![a-z_])[\w.\-]* = "
    r'.*custom_call_target="tpu_custom_call"')


def read(r):
    if not (r.trace and r.trace.ops):
        return None
    per_step = [sum(e - s for name, s, e in ops if KERNEL.match(name))
                for _, _, _, ops in xplane.step_device_work(r.trace, 0)]
    per_step = [ns for ns in per_step if ns]
    return statistics.median(per_step) / 1e6 if per_step else None

"""What a recomputed block keeps of the kernels of ``ray_tpu/ops``: each
kernel module names its forward rule's two outputs beside that rule; here
they meet in one policy. A new kernel adds its names to its own file and one
line here. Kernels that name nothing are made again in a recomputed block:
``rotary``'s prologue pair, ``conv``'s convolutions and ``norm``'s gated
grouped norm, each one pass over its operands.
"""

from __future__ import annotations

import jax

from ray_tpu.ops import attention, delta, sparse_index, ssm


def remat_policy():
    """The policy for ``jax.checkpoint`` / ``nn.remat`` round a block that
    may run a kernel of ``ray_tpu/ops``: keep the selective scan's, the
    scalar-decay scan's and the gated delta rule's output and boundary
    states, and the flash kernel's output and log-sum-exp (per layer one
    [B, T, H, d_v] array in the compute dtype and B x H x T float32; dense
    where the kernels write the model's arrays, else at a value width of 64
    a lane-padded [B x H, T, 64] of nearly twice those bytes: the comment
    above ``attention.REMAT_NAMES``), and of a learned selection
    (``sparse_index.REMAT_NAMES``) the three gradients that the indexer's KL
    kernel makes beside its loss (float32, shaped as the index queries, keys
    and weights) and the selection's mask as ``select`` packed it (a bit a
    pair: int32 [B, T / 32, T], 32 MiB a sequence of 16,384, where a byte a
    pair was 256), recompute everything else. The backward pass of such a
    block then reruns the projections and not the forward kernel, and not
    the selection either: the flash backward reads the kept mask. Where the
    block's attention is not the kernel (``xla``, the scan) no such name
    exists, nothing is kept and the program is the one without a policy."""
    return jax.checkpoint_policies.save_only_these_names(
        *attention.REMAT_NAMES, *ssm.SCAN_REMAT_NAMES, *delta.REMAT_NAMES,
        *ssm.SSD_REMAT_NAMES, *sparse_index.REMAT_NAMES)

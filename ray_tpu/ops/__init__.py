"""ray_tpu.ops — TPU kernels (Pallas), sequence-parallel attention, the
selective and the scalar-decay scan of state-space layers, the gated delta
rule of linear-attention layers, the short convolutions, the gated grouped
norm, expert layers and the vocabulary's loss.

Who knows whom, arrows one way: ``models/*`` -> ``remat`` (what a recomputed
block keeps) and the op modules ``attention`` (its kernels:
``flash_kernels``, and ``rotary``, the kernels of a normed, rotated layer's
prologue), ``ssm``, ``conv``, ``norm``, ``delta``, ``moe`` -> ``chunks`` (the
chunk scheme ``delta`` and ``ssm.ssd_scan`` share) and ``mosaic`` (where a
Pallas kernel may run, how it is handed to a mesh, the compiler's
parameters) -> ``parallel/mesh_utils``. A new kernel module names its own
``REMAT_NAMES`` and adds one line to ``remat``; it imports nothing from
another op's file.
"""

from ray_tpu.ops.attention import (
    attention_reference,
    flash_attention,
    finalize_flash,
    online_block_update,
)
from ray_tpu.ops.conv import causal_conv, gated_short_conv
from ray_tpu.ops.delta import gated_delta_rule
from ray_tpu.ops.norm import gated_group_rms_norm
from ray_tpu.ops.ring_attention import ring_attention, ring_self_attention
from ray_tpu.ops.ssm import selective_scan, ssd_scan
from ray_tpu.ops import conv, delta, moe, norm, ssm, xent

__all__ = [
    "conv",
    "delta",
    "moe",
    "norm",
    "ssm",
    "xent",
    "attention_reference",
    "causal_conv",
    "finalize_flash",
    "flash_attention",
    "gated_delta_rule",
    "gated_group_rms_norm",
    "gated_short_conv",
    "online_block_update",
    "ring_attention",
    "ring_self_attention",
    "selective_scan",
    "ssd_scan",
]

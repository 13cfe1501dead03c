"""ray_tpu.ops — TPU kernels (Pallas), sequence-parallel attention, the
selective scan of state-space layers, the gated short convolution, expert
layers and the vocabulary's loss."""

from ray_tpu.ops.attention import (
    attention_reference,
    flash_attention,
    finalize_flash,
    online_block_update,
)
from ray_tpu.ops.conv import gated_short_conv
from ray_tpu.ops.ring_attention import ring_attention, ring_self_attention
from ray_tpu.ops.ssm import selective_scan
from ray_tpu.ops import conv, moe, ssm, xent

__all__ = [
    "conv",
    "moe",
    "ssm",
    "xent",
    "attention_reference",
    "finalize_flash",
    "flash_attention",
    "gated_short_conv",
    "online_block_update",
    "ring_attention",
    "ring_self_attention",
    "selective_scan",
]

"""Model zoo: flagship TPU-native model families.

- gpt2: the benchmark LM (flash attention, chunked loss, TP/PP/SP builders)
- llama: decoder with RoPE/GQA + KV-cache serving path
- vision: ViT and ResNet
- moe_lm: Switch-Transformer MoE LM (GSPMD expert parallelism)
- mla_moe: latent attention, routed experts held by share, a prediction
  module (training)
- afmoe: window and full attention layers over grouped heads, a gated and
  normed attention, the same routed-expert layer (training)
- phi4flash: blocks of five kinds that hand state down the stack:
  state-space layers (``ops.ssm.selective_scan``), window, full and cross
  differential attention, a gated memory unit (training)
- qwen3_next: gated delta-rule linear-attention layers
  (``ops.delta.gated_delta_rule``) three to one over gated attention,
  softmax-routed experts beside a gated shared expert (training)
- nemotron_h: one mixer a block by a pattern string: Mamba-2 state-space
  mixers (``ops.ssm.ssd_scan``), grouped attention without a positional
  term, sigmoid-routed un-gated relu^2 experts beside a shared expert
  (training)
- mellum: window layers three to one over full layers, rotary positions in
  both from two tables (``llama.rope_table``: plain and YaRN-scaled), every
  feed-forward softmax-routed experts with no shared expert and no dense
  layer (training)
- sdar: block diffusion over two streams, a noisy and a clean copy of a
  sequence under one mask by block (``ops.attention.seen_by_block``), a
  weighted loss over the masked positions, the noise drawn inside the step;
  every feed-forward softmax-routed experts (training)
- keye: every attention layer over the keys a learned indexer picks for each
  query (``ops/sparse_index.py``: index scores, the exact ``topk``-th
  largest a row, the indexer's KL loss), rotary positions of three
  components (``llama.rope_table`` under ``mrope_section``), every
  feed-forward softmax-routed experts (training; the language model of a
  vision-language model, without its tower)
"""

from ray_tpu.models import (afmoe, gpt2, keye, llama, mellum, mla_moe, moe_lm,
                            nemotron_h, phi4flash, qwen3_next, sdar, vision)

__all__ = ["afmoe", "gpt2", "keye", "llama", "mellum", "mla_moe", "moe_lm",
           "nemotron_h", "phi4flash", "qwen3_next", "sdar", "vision"]

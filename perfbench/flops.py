"""Operations a GPT-2 training step needs, from the configuration's sizes.

Kept with the benchmark so that no later PR can move the yardstick. Only
the arithmetic the algorithm requires is counted: a forward and a backward
pass over every weight matrix (2 + 4 operations per parameter per token)
and the attention scores and their use (QK^T and PV, forward and backward).
Operations a step repeats to save memory (per-block recomputation, the
chunked loss recomputing its logits) do not count.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def num_params(model: dict) -> int:
    """Parameters of GPT-2 with a tied output head, from the config's keys."""
    d, layers = model["n_embd"], model["n_layer"]
    inner = model.get("n_inner") or 4 * d
    attn = d * 3 * d + 3 * d + d * d + d        # c_attn, c_proj
    mlp = d * inner + inner + inner * d + d     # c_fc, c_proj
    norms = 2 * 2 * d                           # ln_1, ln_2: scale and bias
    embed = model["vocab_size"] * d + model["n_positions"] * d
    return embed + layers * (attn + mlp + norms) + 2 * d  # + ln_f


def train_flops_per_token(model: dict, seq: int) -> float:
    """6 N + 12 L d T: N counts every parameter (the tied embedding is the
    output head's matrix; the position table's 0.8M are within rounding),
    12 L d T is the attention arithmetic at sequence length T, causal
    masking not discounted (the usual convention, PaLM appendix B)."""
    return (6.0 * num_params(model)
            + 12.0 * model["n_layer"] * model["n_embd"] * seq)


def peaks(device_kind: str) -> dict:
    """The published peaks of one chip. An unknown device is an error."""
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)["by_device_kind"]
    if device_kind not in table:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in perfbench/peaks.json (known: {sorted(table)})")
    return table[device_kind]

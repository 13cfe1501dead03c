"""The state-space, differential-attention, gated-memory family of the
benchmark (``phi4flash``; the contract: ``worker.load_family``).

The program's side is ``ray_tpu.models.phi4flash``, called as a user calls
it: ``init_params``, ``make_optimizer``, ``build_train_step``. The count of
parameters and of operations is the benchmark's own, from the configuration
file's keys alone.

The family's keys, beside the ones every configuration shares, are the
published ones (``hidden_size``, ``intermediate_size``,
``num_attention_heads``, ``num_key_value_heads``, ``sliding_window``,
``layer_norm_eps``), the state-space layer's sizes, which the published file
does not give (``d_state``, ``d_conv``, ``expand``, ``dt_rank``: the file
lists them under ``assumed`` too), ``layer_kinds``, the kind of each of the
``published.num_hidden_layers`` layers by the published rule, whole, and
``kept_layers``, the published indices of the layers run:
``num_hidden_layers`` is their number. ``vocab_size`` is the slice of the
vocabulary resident, in the tied embedding and head.
``initializer_range`` is assumed (the file says so); ``train.attention``,
``train.loss_chunks`` and the traffic's ``remat`` are the program's
options.

Operations a token (``train_flops_per_token``): a forward and a backward
pass over every weight matrix the token passes through (6 a parameter; the
convolution's taps among them), the tied head once, the embedding's lookups
and the norms not at all; attention's scores and their use by the pairs each
layer's own mask leaves, exactly, as ``families/afmoe.py`` counts them:
each of a differential layer's two maps has ``num_attention_heads / 2``
heads of keys ``d`` and values ``2 d`` wide, ``6 (d + 2 d)`` a pair and
head, ``(T + 1) / 2`` pairs a token in the full and the cross layers and
``(W T - W (W - 1) / 2) / T`` in a window layer of ``W`` keys; and the
recurrence, 22 operations a position, channel and state (7 forward, 15
backward: ``metrics/ssm_scan_roofline_pct.py`` names them), 1.8 M a token
and layer beside 240 M in the layer's matrices. Recomputed operations (a
block's forward pass again, the chunk's states again in the backward scan)
do not count.
"""

from __future__ import annotations

import types

SSM, WINDOW, FULL, GMU, CROSS = "ssm", "window", "full", "gmu", "cross"
SCAN_OPS = 22


def kinds_run(m: dict) -> tuple:
    """The kinds of the layers run, from the file's whole list."""
    return tuple(m["layer_kinds"][i] for i in m["kept_layers"])


def _sizes(m: dict) -> dict:
    d, inter = m["hidden_size"], m["intermediate_size"]
    heads, kv = m["num_attention_heads"], m["num_key_value_heads"]
    head, inner = d // heads, m["expand"] * d
    n, taps, rank = m["d_state"], m["d_conv"], m["dt_rank"]
    q_o = 2 * d * heads * head          # Wq and Wo
    k_v = 2 * d * kv * head
    # what no token is multiplied with: biases, lam's vectors, subln
    attn_rest = heads * head + d + 4 * head + 2 * head
    return {
        "mlp": 3 * d * inter, "norms": 4 * d,
        # matrices a token is multiplied with, by kind
        "matmul": {
            SSM: (2 * d * inner + taps * inner + inner * (rank + 2 * n)
                  + rank * inner + inner * d),
            WINDOW: q_o + k_v, FULL: q_o + k_v, CROSS: q_o,
            GMU: 2 * d * inner},
        "rest": {
            SSM: inner * (3 + n),       # conv bias, dt bias, D; A_log
            WINDOW: attn_rest + 2 * kv * head, FULL: attn_rest + 2 * kv * head,
            CROSS: attn_rest, GMU: 0},
        "table": m["vocab_size"] * d,
    }


def num_params(m: dict) -> int:
    s = _sizes(m)
    return (s["table"] + 2 * m["hidden_size"]             # final LayerNorm
            + sum(s["mlp"] + s["norms"] + s["matmul"][kind] + s["rest"][kind]
                  for kind in kinds_run(m)))


def matmul_params_per_token(m: dict) -> int:
    s = _sizes(m)
    return s["table"] + sum(s["mlp"] + s["matmul"][kind]
                            for kind in kinds_run(m))


def attended_pairs_per_token(m: dict, seq: int) -> float:
    """Query-key pairs a head of one map, a token, summed over the layers
    run: what each layer's own mask leaves of ``seq`` tokens."""
    window = min(m["sliding_window"], seq)
    pairs = {WINDOW: (window * seq - window * (window - 1) / 2) / seq,
             FULL: (seq + 1) / 2, CROSS: (seq + 1) / 2}
    return sum(pairs.get(kind, 0.0) for kind in kinds_run(m))


def train_flops_per_token(m: dict, seq: int) -> float:
    head = m["hidden_size"] // m["num_attention_heads"]
    # two maps of num_attention_heads / 2 heads, keys d and values 2 d wide
    attention = (6.0 * m["num_attention_heads"] * 3 * head
                 * attended_pairs_per_token(m, seq))
    scan = (SCAN_OPS * m["expand"] * m["hidden_size"] * m["d_state"]
            * kinds_run(m).count(SSM))
    return 6.0 * matmul_params_per_token(m) + attention + scan


def build(model: dict, traffic: dict, mesh):
    import jax.numpy as jnp

    from ray_tpu.models import phi4flash

    recipe, layers = model["train"], model["published"]["num_hidden_layers"]
    if (tuple(model["layer_kinds"]) != phi4flash.layer_kinds(layers)
            or len(model["kept_layers"]) != model["num_hidden_layers"]):
        raise ValueError("layer_kinds is not the published rule's list, or "
                         "num_hidden_layers not the number of kept_layers")
    cfg = phi4flash.Phi4FlashConfig(
        num_hidden_layers=layers, kept_layers=tuple(model["kept_layers"]),
        dtype=jnp.dtype(recipe["compute_dtype"]),
        remat=bool(traffic.get("remat")), attention=recipe["attention"],
        loss_chunks=recipe["loss_chunks"],
        **{key: model[key] for key in (
            "vocab_size", "hidden_size", "intermediate_size",
            "num_attention_heads", "num_key_value_heads", "sliding_window",
            "layer_norm_eps", "d_state", "d_conv", "expand", "dt_rank",
            "initializer_range")})
    net = phi4flash.Phi4Flash(cfg)
    tx = phi4flash.make_optimizer(recipe["learning_rate"],
                                  recipe["weight_decay"])

    def make_state(key):
        params = phi4flash.init_params(cfg, key)[1]
        return params, tx.init(params)

    return types.SimpleNamespace(
        make_state=make_state,
        param_shardings=lambda shapes: phi4flash.param_shardings(shapes,
                                                                 mesh),
        place_state=lambda params, opt_state: phi4flash.shard_train_state(
            params, opt_state, mesh),
        step=phi4flash.build_train_step(net, tx, donate=True))

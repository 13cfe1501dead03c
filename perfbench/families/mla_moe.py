"""The latent-attention, routed-expert family of the benchmark (the contract:
``worker.load_family``).

The program's side is ``ray_tpu.models.mla_moe``, called as a user calls
it: ``init_params``, ``make_optimizer``, ``shard_train_state``,
``parallel.build_train_step`` over its ``loss_fn``, which returns the loss
and its parts; the adapter narrows it to the loss. The count of parameters and of operations is the
benchmark's own, from the configuration file's keys alone.

The family's keys, beside the ones every configuration shares, are the
published ones (``hidden_size``, ``num_hidden_layers``,
``num_attention_heads``, ``q_lora_rank``, ``kv_lora_rank``,
``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``,
``intermediate_size``, ``moe_intermediate_size``, ``first_k_dense_replace``,
``n_shared_experts``, ``num_experts_per_tok``, ``routed_scaling_factor``,
``norm_topk_prob``, ``num_nextn_predict_layers``, ``rope_theta``,
``rms_norm_eps``) and the share of the deployment this chip holds:
``n_routed_experts`` is the number of routed experts HELD in each expert
layer, ``n_routed_experts_published`` the router's width, ``expert_shard``
``{index, of}`` which slice they are; ``vocab_size`` is the slice of the
vocabulary resident. ``mtp_loss_weight`` and ``initializer_range`` are
assumed (the file says so); ``train.attention``, ``train.loss_chunks`` and
the traffic's ``remat`` are the program's options.

Operations a token (``train_flops_per_token``): a forward and a backward
pass over every weight matrix the token passes through (6 a parameter),
the routed experts by their expectation under uniform routing,
``num_experts_per_tok x held / published`` experts a layer (here half an
expert: the other 7.5 a token would use are on other chips); the head twice
(once a loss term); the embedding's lookups and the norms' scales not at
all; and attention's scores and their use, ``6 T H (d_qk + d_v)`` a layer,
the causal mask not discounted, as the other cells count. Recomputed
operations do not count.
"""

from __future__ import annotations

import types


def _sizes(m: dict) -> dict:
    d, heads = m["hidden_size"], m["num_attention_heads"]
    d_qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    width = m["moe_intermediate_size"]
    attn = (d * m["q_lora_rank"] + m["q_lora_rank"] * heads * d_qk
            + d * (m["kv_lora_rank"] + m["qk_rope_head_dim"])
            + m["kv_lora_rank"] * heads
            * (m["qk_nope_head_dim"] + m["v_head_dim"])
            + heads * m["v_head_dim"] * d)
    return {
        "attn": attn,                                     # its five matrices
        "attn_norms": m["q_lora_rank"] + m["kv_lora_rank"],
        "block_norms": 2 * d,
        "dense_mlp": 3 * d * m["intermediate_size"],
        "router": d * m["n_routed_experts_published"],
        "router_bias": m["n_routed_experts_published"],
        "expert": 3 * d * width,
        "shared": 3 * d * width * m["n_shared_experts"],
        "dense_layers": m["first_k_dense_replace"],
        "expert_layers": (m["num_hidden_layers"] - m["first_k_dense_replace"]
                          + m["num_nextn_predict_layers"]),
        "table": m["vocab_size"] * d,
        "eh_proj": 2 * d * d,
    }


def num_params(m: dict) -> int:
    s = _sizes(m)
    around = s["attn"] + s["attn_norms"] + s["block_norms"]
    expert_layer = (around + s["router"] + s["router_bias"] + s["shared"]
                    + m["n_routed_experts"] * s["expert"])
    mtp = m["num_nextn_predict_layers"] * (
        s["eh_proj"] + 3 * m["hidden_size"])     # enorm, hnorm, the last norm
    return (2 * s["table"] + m["hidden_size"]     # embedding, head, final norm
            + s["dense_layers"] * (around + s["dense_mlp"])
            + s["expert_layers"] * expert_layer + mtp)


def matmul_params_per_token(m: dict) -> float:
    """The parameters of the matrices one token is multiplied with, the
    routed experts by their expectation on this chip."""
    s = _sizes(m)
    routed = (m["num_experts_per_tok"] * m["n_routed_experts"]
              / m["n_routed_experts_published"]) * s["expert"]
    terms = 1 + m["num_nextn_predict_layers"]
    return (s["dense_layers"] * (s["attn"] + s["dense_mlp"])
            + s["expert_layers"] * (s["attn"] + s["router"] + s["shared"]
                                    + routed)
            + m["num_nextn_predict_layers"] * s["eh_proj"]
            + terms * s["table"])


def train_flops_per_token(m: dict, seq: int) -> float:
    layers = m["num_hidden_layers"] + m["num_nextn_predict_layers"]
    d_qk = m["qk_nope_head_dim"] + m["qk_rope_head_dim"]
    attention = (6.0 * seq * m["num_attention_heads"]
                 * (d_qk + m["v_head_dim"]) * layers)
    return 6.0 * matmul_params_per_token(m) + attention


def build(model: dict, traffic: dict, mesh):
    import jax.numpy as jnp

    from ray_tpu import parallel
    from ray_tpu.models import mla_moe

    recipe, shard = model["train"], model["expert_shard"]
    if model["n_routed_experts"] * shard["of"] != model[
            "n_routed_experts_published"]:
        raise ValueError("n_routed_experts (held) x expert_shard.of is not "
                         "n_routed_experts_published")
    cfg = mla_moe.MLAMoEConfig(
        n_routed_experts=model["n_routed_experts_published"],
        expert_shard=(shard["index"], shard["of"]),
        dtype=jnp.dtype(recipe["compute_dtype"]),
        remat=bool(traffic.get("remat")), attention=recipe["attention"],
        loss_chunks=recipe["loss_chunks"],
        **{key: model[key] for key in (
            "vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "q_lora_rank", "kv_lora_rank",
            "qk_nope_head_dim", "qk_rope_head_dim", "v_head_dim",
            "intermediate_size", "moe_intermediate_size",
            "first_k_dense_replace", "n_shared_experts",
            "num_experts_per_tok", "routed_scaling_factor", "norm_topk_prob",
            "num_nextn_predict_layers", "rope_theta", "rms_norm_eps",
            "initializer_range", "mtp_loss_weight")})
    net = mla_moe.MLAMoE(cfg)
    tx = mla_moe.make_optimizer()

    def loss(params, batch):
        # the program's loss returns (loss, its two terms and the tokens
        # each held expert received); the worker takes the loss
        return mla_moe.loss_fn(params, net, batch)[0]

    def make_state(key):
        params = mla_moe.init_params(cfg, key)[1]
        return params, tx.init(params)

    return types.SimpleNamespace(
        make_state=make_state,
        param_shardings=lambda shapes: mla_moe.param_shardings(shapes, mesh),
        place_state=lambda params, opt_state: mla_moe.shard_train_state(
            params, opt_state, mesh),
        step=parallel.build_train_step(loss, tx, donate=True),
        # beyond the contract, for a builder's side run: the loss with its
        # parts and the tokens each held expert received
        loss_with_parts=lambda params, batch: mla_moe.loss_fn(
            params, net, batch))

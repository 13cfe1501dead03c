"""The gated-delta-rule, gated-attention, softmax-routed-expert family of the
benchmark (``qwen3_next``; the contract: ``worker.load_family``).

The program's side is ``ray_tpu.models.qwen3_next``, called as a user calls
it: ``init_params``, ``make_optimizer``, ``build_train_step`` (the loss and
the tokens each held expert received), ``step_metrics`` where the loop reads
its loss. The count of parameters and of operations is the benchmark's own,
from the configuration file's keys alone.

The family's keys, beside the ones every configuration shares, are the
published ones (``hidden_size``, ``head_dim``, ``num_attention_heads``,
``num_key_value_heads``, ``partial_rotary_factor``, ``rope_theta``,
``full_attention_interval``, the five ``linear_*`` sizes,
``moe_intermediate_size``, ``shared_expert_intermediate_size``,
``num_experts_per_tok``, ``norm_topk_prob``, ``rms_norm_eps``) and the share
of the deployment this chip holds: ``layer_types`` is the published list,
whole (``full_attention`` where ``(i + 1) % full_attention_interval == 0``),
and ``kept_layers`` the published indices of the layers run
(``num_hidden_layers`` of them); ``num_experts`` is the number of routed
experts HELD in each layer, ``num_experts_published`` the router's width,
``expert_shard`` ``{index, of}`` which slice they are; ``vocab_size`` is the
slice of the vocabulary resident, in the embedding and in the untied head.
``initializer_range`` is assumed (the file says so); ``train.attention``,
``train.loss_chunks`` and the traffic's ``remat`` are the program's options.

The held experts' load is held level, by the recipe and for the reasons of
``perfbench/families/afmoe.py`` (``train.selection_bias``; a recipe of the
benchmark here, the published router has no selection bias: zero is the
published router): the state a run starts from has the held experts'
selection bias levelled on the cell's one batch (``levelled``), after every
step the balance update moves it by ``selection_bias.update_rate`` against
the load the step reported (``rebalanced``), and the learning rate climbs
linearly over ``train.lr_warmup_steps``. The reference is handed the same
bias with the parameters.

Operations a token (``train_flops_per_token``): a forward and a backward
pass over every weight matrix the token passes through (6 a parameter), the
routed experts by their expectation under uniform routing,
``num_experts_per_tok x held / published`` experts a layer; the head once
(the embedding's lookups not at all, nor the norms' scales, the taps, the
decays); attention's scores and their use by the pairs the causal mask
leaves, ``6 H 2 D`` a pair and ``(T + 1) / 2`` pairs a token in each
``full_attention`` layer; in a ``linear_attention`` layer the rule's
recurrence at what it needs one position a step, ``RULE_OPS`` = 22 an entry
of a value head's [d_k, d_v] state (7 forward, 15 backward:
``perfbench/metrics/delta_rule_roofline_pct.py`` says which), whatever the
chunked form spends, and the convolution's ``CONV_OPS`` = 31 a channel (the
four products, three sums and the SiLU forward: 11; 20 backward).
Recomputed operations do not count.
"""

from __future__ import annotations

import types

LINEAR, FULL = "linear_attention", "full_attention"
RULE_OPS = 22
CONV_OPS = 31


def layers_run(m: dict) -> tuple:
    """(published index, kind) of the layers run."""
    kept = m["kept_layers"]
    if len(kept) != m["num_hidden_layers"]:
        raise ValueError("kept_layers does not name num_hidden_layers layers")
    every = m["full_attention_interval"]
    for i, kind in enumerate(m["layer_types"]):
        if kind != (FULL if (i + 1) % every == 0 else LINEAR):
            raise ValueError(f"layer_types[{i}] is not the published rule's")
    return tuple((i, m["layer_types"][i]) for i in kept)


def _sizes(m: dict) -> dict:
    d, heads, kv, dim = (m["hidden_size"], m["num_attention_heads"],
                         m["num_key_value_heads"], m["head_dim"])
    keys = m["linear_num_key_heads"] * m["linear_key_head_dim"]
    values = m["linear_num_value_heads"] * m["linear_value_head_dim"]
    return {
        # q, k, v and z; b and a; the out-projection
        "linear": (d * (2 * keys + 2 * values)
                   + d * 2 * m["linear_num_value_heads"] + values * d),
        # the taps, A_log and dt_bias, the gated norm's scale
        "linear_rest": (m["linear_conv_kernel_dim"] * (2 * keys + values)
                        + 2 * m["linear_num_value_heads"]
                        + m["linear_value_head_dim"]),
        "conv_channels": 2 * keys + values,
        "rule_entries": (m["linear_num_value_heads"]
                         * m["linear_key_head_dim"]
                         * m["linear_value_head_dim"]),
        # q with its gate, o; k and v over their own heads
        "attn": d * heads * 2 * dim + heads * dim * d + 2 * d * kv * dim,
        "head_norms": 2 * dim,
        "block_norms": 2 * d,
        "router": d * m["num_experts_published"],
        "router_bias": m["num_experts_published"],
        "shared": 3 * d * m["shared_expert_intermediate_size"] + d,  # + gate
        "expert": 3 * d * m["moe_intermediate_size"],
        "table": m["vocab_size"] * d,
    }


def num_params(m: dict) -> int:
    s = _sizes(m)
    mixer = {LINEAR: s["linear"] + s["linear_rest"],
             FULL: s["attn"] + s["head_norms"]}
    layer = (s["block_norms"] + s["router"] + s["router_bias"] + s["shared"]
             + m["num_experts"] * s["expert"])
    return (2 * s["table"] + m["hidden_size"]   # embedding, head, final norm
            + sum(mixer[kind] + layer for _, kind in layers_run(m)))


def matmul_params_per_token(m: dict) -> float:
    """The parameters of the matrices one token is multiplied with, the
    routed experts by their expectation on this chip."""
    s = _sizes(m)
    routed = (m["num_experts_per_tok"] * m["num_experts"]
              / m["num_experts_published"]) * s["expert"]
    mixer = {LINEAR: s["linear"], FULL: s["attn"]}
    return s["table"] + sum(
        mixer[kind] + s["router"] + s["shared"] + routed
        for _, kind in layers_run(m))


def train_flops_per_token(m: dict, seq: int) -> float:
    s = _sizes(m)
    kinds = [kind for _, kind in layers_run(m)]
    attention = (6.0 * m["num_attention_heads"] * 2 * m["head_dim"]
                 * (seq + 1) / 2 * kinds.count(FULL))
    linear = float((RULE_OPS * s["rule_entries"]
                    + CONV_OPS * s["conv_channels"]) * kinds.count(LINEAR))
    return 6.0 * matmul_params_per_token(m) + attention + linear


def build(model: dict, traffic: dict, mesh):
    import jax
    import jax.numpy as jnp
    import optax

    from perfbench import traffic as traffic_mod
    from perfbench.families.afmoe import _Narrowed
    from ray_tpu.models import qwen3_next

    recipe, shard = model["train"], model["expert_shard"]
    if model["num_experts"] * shard["of"] != model["num_experts_published"]:
        raise ValueError("num_experts (held) x expert_shard.of is not "
                         "num_experts_published")
    layers = layers_run(model)
    cfg = qwen3_next.Qwen3NextConfig(
        num_hidden_layers=len(model["layer_types"]),
        num_experts=model["num_experts_published"],
        expert_shard=(shard["index"], shard["of"]),
        layer_types=tuple(model["layer_types"]),
        kept_layers=tuple(model["kept_layers"]),
        dtype=jnp.dtype(recipe["compute_dtype"]),
        remat=bool(traffic.get("remat")), attention=recipe["attention"],
        loss_chunks=recipe["loss_chunks"],
        **{key: model[key] for key in (
            "vocab_size", "hidden_size", "full_attention_interval",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "partial_rotary_factor", "rope_theta", "linear_num_key_heads",
            "linear_num_value_heads", "linear_key_head_dim",
            "linear_value_head_dim", "linear_conv_kernel_dim",
            "moe_intermediate_size", "shared_expert_intermediate_size",
            "num_experts_per_tok", "norm_topk_prob", "rms_norm_eps",
            "initializer_range")})
    net = qwen3_next.Qwen3Next(cfg)
    tx = qwen3_next.make_optimizer(optax.linear_schedule(
        0.0, recipe["learning_rate"], recipe["lr_warmup_steps"]))
    pairs = traffic["batch"] * traffic["seq"] * model["num_experts_per_tok"]
    held, level = model["num_experts"], recipe["selection_bias"]
    mine = slice(shard["index"] * held, (shard["index"] + 1) * held)
    share = pairs / model["num_experts_published"]
    names = [f"layers_{i}" for i, _ in layers]

    def with_bias(params, bias):
        """``params`` with row i of ``bias`` as layer i's selection bias."""
        out = dict(params)
        for name, row in zip(names, bias):
            out[name] = {**out[name],
                         "moe": {**out[name]["moe"], "router_bias": row}}
        return out

    def moved(bias, load, rate):
        """The balance update, for the held experts' entries of ``bias``
        [layers, experts] (the others' loads are other chips' to see): up by
        ``rate`` under the uniform share of ``load`` [layers, held], down
        above it."""
        return bias.at[:, mine].add(rate * jnp.sign(share - load))

    def resident_ids(key_data):
        # the cell's one batch, as ``run.py`` makes it from ``--seed``: the
        # worker's key is ``PRNGKey(seed)``, whose last word is the seed
        return traffic_mod.resident_tokens(
            int(key_data[-1]), traffic, model["vocab_size"])[:, :-1]

    def levelled(params, key):
        """``params`` with the held experts' selection bias moved until
        each receives its uniform share of the cell's batch: the update
        swept over the batch at a falling rate."""
        ids = jax.pure_callback(
            resident_ids, jax.ShapeDtypeStruct(
                (traffic["batch"], traffic["seq"]), jnp.int32),
            jax.random.key_data(key))

        def sweep(i, bias):
            load = net.apply({"params": with_bias(params, bias)}, ids)[1]
            return moved(bias, load, level["rate"] * level["decay"] ** i)

        return with_bias(params, jax.lax.fori_loop(
            0, level["sweeps"], sweep, jnp.zeros(
                (len(names), model["num_experts_published"]), jnp.float32)))

    rebalanced = jax.jit(lambda rows, load: tuple(moved(
        jnp.stack(rows), load, level["update_rate"])))

    def between(params, out):
        rows = [params[name]["moe"]["router_bias"] for name in names]
        return with_bias(params, rebalanced(rows, jax.tree.leaves(out)[1]))

    def make_state(key):
        params = levelled(qwen3_next.init_params(cfg, key)[1], key)
        return params, tx.init(params)

    def narrow(out):
        # what the step returns besides the loss goes where a user's loop
        # sends it, at the point where the loop reads its loss: the
        # ``train/step_aux`` record of the runtime's ring
        loss, tokens = jax.tree.leaves(out)
        return qwen3_next.step_metrics(loss, tokens, pairs=pairs)["loss"]

    return types.SimpleNamespace(
        make_state=make_state,
        param_shardings=lambda shapes: qwen3_next.param_shardings(
            shapes, mesh),
        place_state=lambda params, opt_state: qwen3_next.shard_train_state(
            params, opt_state, mesh),
        step=_Narrowed(qwen3_next.build_train_step(net, tx, donate=True),
                       between, narrow),
        # beyond the contract, for a builder's side run: the loss and the
        # tokens each held expert received
        loss_with_parts=lambda params, batch: qwen3_next.loss_fn(
            params, net, batch))

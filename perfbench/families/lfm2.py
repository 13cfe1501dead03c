"""The short-convolution, grouped-attention, routed-expert family of the
benchmark (``lfm2_moe``; the contract: ``worker.load_family``).

The program's side is ``ray_tpu.models.lfm2``, called as a user calls it:
``init_params``, ``make_optimizer``, ``build_train_step`` (the loss and the
tokens each held expert received), ``step_metrics`` where the loop reads its
loss. The count of parameters and of operations is the benchmark's own, from
the configuration file's keys alone.

The family's keys, beside the ones every configuration shares, are the
published ones (``hidden_size``, ``intermediate_size``,
``moe_intermediate_size``, ``num_attention_heads``, ``num_key_value_heads``,
``conv_L_cache``, ``layer_types``, ``num_experts_per_tok``,
``norm_topk_prob``, ``routed_scaling_factor``, ``norm_eps``,
``rope_theta``) and the share of the deployment this chip holds:
``layer_types`` is the published list, whole, and ``kept_layers`` the
published indices of the layers run (``num_hidden_layers`` of them), the
first ``num_dense_layers`` of which have the dense feed-forward;
``num_experts`` is the number of routed experts HELD in each expert layer,
``num_experts_published`` the router's width, ``expert_shard`` ``{index,
of}`` which slice they are; ``vocab_size`` is the slice of the vocabulary
resident, in the embedding and so in the head tied to it.
``initializer_range`` is assumed (the file says so); ``train.attention``,
``train.loss_chunks`` and the traffic's ``remat`` are the program's options.

The held experts' load is held level, by the recipe and for the reasons of
``perfbench/families/afmoe.py`` (``train.selection_bias``): the state a run
starts from has the held experts' selection bias levelled on the cell's one
batch (``levelled``), after every step the balance update moves it by
``selection_bias.update_rate`` against the load the step reported
(``rebalanced``), and the learning rate climbs linearly over
``train.lr_warmup_steps``.

Operations a token (``train_flops_per_token``): a forward and a backward
pass over every weight matrix the token passes through (6 a parameter), the
routed experts by their expectation under uniform routing,
``num_experts_per_tok x held / published`` experts a layer; the tied table
once, as the head (the embedding's lookups not at all, nor the norms'
scales); attention's scores and their use by the pairs the causal mask
leaves, ``6 H 2 D`` a pair and ``(T + 1) / 2`` pairs a token in each
``full_attention`` layer, nothing of the kind in a ``conv`` layer, whose
own arithmetic is 22 operations a channel (7 forward, 15 backward).
Recomputed operations do not count.
"""

from __future__ import annotations

import types

CONV, FULL = "conv", "full_attention"
CONV_OPS = 22


def layers_run(m: dict) -> tuple:
    """(published index, kind, dense feed-forward) of the layers run."""
    kept = m["kept_layers"]
    if len(kept) != m["num_hidden_layers"]:
        raise ValueError("kept_layers does not name num_hidden_layers layers")
    return tuple((i, m["layer_types"][i], n < m["num_dense_layers"])
                 for n, i in enumerate(kept))


def _sizes(m: dict) -> dict:
    d, heads, kv = (m["hidden_size"], m["num_attention_heads"],
                    m["num_key_value_heads"])
    head = d // heads
    return {
        # in-projection to B, C, x and the out-projection; the taps apart
        "conv": 4 * d * d,
        "taps": m["conv_L_cache"] * d,
        # q and o over the query heads; k and v over their own
        "attn": 2 * d * heads * head + 2 * d * kv * head,
        "head_norms": 2 * head,
        "block_norms": 2 * d,
        "dense_mlp": 3 * d * m["intermediate_size"],
        "router": d * m["num_experts_published"],
        "router_bias": m["num_experts_published"],
        "expert": 3 * d * m["moe_intermediate_size"],
        "table": m["vocab_size"] * d,
    }


def _by_layer(m: dict, operator, feed_forward) -> float:
    return sum(operator[kind] + feed_forward[dense]
               for _, kind, dense in layers_run(m))


def num_params(m: dict) -> int:
    s = _sizes(m)
    operator = {CONV: s["conv"] + s["taps"], FULL: s["attn"] + s["head_norms"]}
    feed_forward = {True: s["dense_mlp"],
                    False: (s["router"] + s["router_bias"]
                            + m["num_experts"] * s["expert"])}
    return (s["table"] + m["hidden_size"]       # the tied table, final norm
            + len(layers_run(m)) * s["block_norms"]
            + _by_layer(m, operator, feed_forward))


def matmul_params_per_token(m: dict) -> float:
    """The parameters of the matrices one token is multiplied with, the
    routed experts by their expectation on this chip."""
    s = _sizes(m)
    routed = (m["num_experts_per_tok"] * m["num_experts"]
              / m["num_experts_published"]) * s["expert"]
    return s["table"] + _by_layer(
        m, {CONV: s["conv"], FULL: s["attn"]},
        {True: s["dense_mlp"], False: s["router"] + routed})


def train_flops_per_token(m: dict, seq: int) -> float:
    kinds = [kind for _, kind, _ in layers_run(m)]
    attention = (6.0 * 2 * m["hidden_size"] * (seq + 1) / 2
                 * kinds.count(FULL))
    convolution = float(CONV_OPS * m["hidden_size"] * kinds.count(CONV))
    return 6.0 * matmul_params_per_token(m) + attention + convolution


def build(model: dict, traffic: dict, mesh):
    import jax
    import jax.numpy as jnp
    import optax

    from perfbench import traffic as traffic_mod
    from perfbench.families.afmoe import _Narrowed
    from ray_tpu.models import lfm2

    recipe, shard = model["train"], model["expert_shard"]
    if model["num_experts"] * shard["of"] != model["num_experts_published"]:
        raise ValueError("num_experts (held) x expert_shard.of is not "
                         "num_experts_published")
    layers = layers_run(model)
    cfg = lfm2.Lfm2Config(
        num_experts=model["num_experts_published"],
        expert_shard=(shard["index"], shard["of"]),
        layer_types=tuple(model["layer_types"]),
        kept_layers=tuple(model["kept_layers"]),
        dtype=jnp.dtype(recipe["compute_dtype"]),
        remat=bool(traffic.get("remat")), attention=recipe["attention"],
        loss_chunks=recipe["loss_chunks"],
        route_eps=model["route_eps"],
        **{key: model[key] for key in (
            "vocab_size", "hidden_size", "intermediate_size",
            "moe_intermediate_size", "num_attention_heads",
            "num_key_value_heads", "conv_L_cache", "num_dense_layers",
            "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
            "norm_eps", "rope_theta", "initializer_range")})
    net = lfm2.Lfm2(cfg)
    tx = lfm2.make_optimizer(optax.linear_schedule(
        0.0, recipe["learning_rate"], recipe["lr_warmup_steps"]))
    pairs = traffic["batch"] * traffic["seq"] * model["num_experts_per_tok"]
    held, level = model["num_experts"], recipe["selection_bias"]
    mine = slice(shard["index"] * held, (shard["index"] + 1) * held)
    share = pairs / model["num_experts_published"]
    expert_layers = [f"layers_{i}" for i, _, dense in layers if not dense]

    def with_bias(params, bias):
        """``params`` with row i of ``bias`` as expert layer i's selection
        bias."""
        out = dict(params)
        for name, row in zip(expert_layers, bias):
            out[name] = {**out[name],
                         "moe": {**out[name]["moe"], "router_bias": row}}
        return out

    def moved(bias, load, rate):
        """The balance update, for the held experts' entries of ``bias``
        [expert layers, experts] (the others' loads are other chips' to
        see): up by ``rate`` under the uniform share of ``load`` [expert
        layers, held], down above it."""
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
                (len(expert_layers), model["num_experts_published"]),
                jnp.float32)))

    rebalanced = jax.jit(lambda rows, load: tuple(moved(
        jnp.stack(rows), load, level["update_rate"])))

    def between(params, out):
        rows = [params[name]["moe"]["router_bias"] for name in expert_layers]
        return with_bias(params, rebalanced(rows, jax.tree.leaves(out)[1]))

    def make_state(key):
        params = levelled(lfm2.init_params(cfg, key)[1], key)
        return params, tx.init(params)

    def narrow(out):
        # what the step returns besides the loss goes where a user's loop
        # sends it, at the point where the loop reads its loss: the
        # ``train/step_aux`` record of the runtime's ring
        loss, tokens = jax.tree.leaves(out)
        return lfm2.step_metrics(loss, tokens, pairs=pairs)["loss"]

    return types.SimpleNamespace(
        make_state=make_state,
        param_shardings=lambda shapes: lfm2.param_shardings(shapes, mesh),
        place_state=lambda params, opt_state: lfm2.shard_train_state(
            params, opt_state, mesh),
        step=_Narrowed(lfm2.build_train_step(net, tx, donate=True), between,
                       narrow),
        # beyond the contract, for a builder's side run: the loss and the
        # tokens each held expert received
        loss_with_parts=lambda params, batch: lfm2.loss_fn(
            params, net, batch))

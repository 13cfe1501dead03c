"""The family of the benchmark that trains by block diffusion: every step
reads a noisy and a clean copy of each sequence under one mask by block,
and every feed-forward part is a softmax-routed expert layer (``sdar``; the
contract: ``worker.load_family``).

The program's side is ``ray_tpu.models.sdar``, called as a user calls it:
``init_params``, ``make_optimizer``, ``build_train_step`` (the loss, the
step's share of masked positions and the tokens each held expert received),
``step_metrics`` where the loop reads its loss. The count of parameters and
of operations is the benchmark's own, from the configuration file's keys
alone.

The family's keys, beside the ones every configuration shares, are the
published ones (``hidden_size``, ``num_hidden_layers``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``moe_intermediate_size``, ``num_experts_per_tok``, ``norm_topk_prob``,
``rope_theta``, ``rms_norm_eps``), the objective's, which the published
config does not give and the file lists under ``assumed``
(``block_length``, ``noise_eps``, ``noise_seed``, ``mask_token_id``), and
the share of the deployment this chip holds: ``num_experts`` is the number
of routed experts HELD in each layer, ``num_experts_published`` the
router's width, ``expert_shard`` ``{index, of}`` which slice they are;
``vocab_size`` is the slice of the vocabulary resident, in the embedding and
in the untied head. ``initializer_range`` is assumed (the file says so);
``train.attention``, ``train.loss_chunks`` and the traffic's ``remat`` are
the program's options.

The held experts' load is held level by the recipe and for the reasons of
``perfbench/families/afmoe.py`` (``train.selection_bias``; a recipe of the
benchmark, as in ``mellum.py``: the published router has no selection bias,
zero is the published router), on the two streams of the cell's one batch
under the first step's noise.

Operations a DATA token (``train_flops_per_token``; a step's tokens are
``batch x seq``, what a user is billed by, and the program runs two
positions for each): a forward and a backward pass over every weight matrix
a position passes through (6 a parameter), BOTH streams through every
layer, the routed experts by their expectation under uniform routing
(``num_experts_per_tok x held / published`` experts a layer); the head once,
over the noisy stream alone; the embedding's lookups, the norms' scales and
the rotations not at all; and attention's scores and their use by the pairs
the mask leaves, exactly: ``6 H 2 D`` a pair, ``seq^2 + seq x
block_length`` pairs a head a sequence (``live_pairs``). Recomputed
operations do not count.
"""

from __future__ import annotations

import types


def _sizes(m: dict) -> dict:
    d, heads, kv, head = (m["hidden_size"], m["num_attention_heads"],
                          m["num_key_value_heads"], m["head_dim"])
    return {
        # q and o over the query heads; k and v over their own
        "attn": 2 * d * heads * head + 2 * d * kv * head,
        "head_norms": 2 * head,
        "block_norms": 2 * d,
        "router": d * m["num_experts_published"],
        "router_bias": m["num_experts_published"],
        "expert": 3 * d * m["moe_intermediate_size"],
        "table": m["vocab_size"] * d,
    }


def num_params(m: dict) -> int:
    s = _sizes(m)
    layer = (s["attn"] + s["head_norms"] + s["block_norms"] + s["router"]
             + s["router_bias"] + m["num_experts"] * s["expert"])
    return (2 * s["table"] + m["hidden_size"]     # embedding, head, final norm
            + m["num_hidden_layers"] * layer)


def matmul_params_per_token(m: dict) -> float:
    """The parameters of the matrices one DATA token is multiplied with:
    its two positions through every layer (the routed experts by their
    expectation on this chip), its noisy position through the head."""
    s = _sizes(m)
    routed = (m["num_experts_per_tok"] * m["num_experts"]
              / m["num_experts_published"]) * s["expert"]
    return (s["table"] + 2 * m["num_hidden_layers"]
            * (s["attn"] + s["router"] + routed))


def live_pairs(seq: int, block_length: int) -> int:
    """Query-key pairs a head that the mask leaves of one sequence's two
    streams: noisy on noisy ``seq x block``, noisy on clean ``seq (seq -
    block) / 2``, clean on clean ``seq (seq + block) / 2``."""
    return seq * seq + seq * block_length


def train_flops_per_token(m: dict, seq: int) -> float:
    attention = (6.0 * m["num_attention_heads"] * 2 * m["head_dim"]
                 * m["num_hidden_layers"]
                 * live_pairs(seq, m["block_length"]) / seq)
    return 6.0 * matmul_params_per_token(m) + attention


def build(model: dict, traffic: dict, mesh):
    import jax
    import jax.numpy as jnp
    import optax

    from perfbench import traffic as traffic_mod
    from perfbench.families.afmoe import _Narrowed
    from ray_tpu.models import sdar

    recipe, shard = model["train"], model["expert_shard"]
    if model["num_experts"] * shard["of"] != model["num_experts_published"]:
        raise ValueError("num_experts (held) x expert_shard.of is not "
                         "num_experts_published")
    cfg = sdar.SdarConfig(
        num_experts=model["num_experts_published"],
        expert_shard=(shard["index"], shard["of"]),
        dtype=jnp.dtype(recipe["compute_dtype"]),
        remat=bool(traffic.get("remat")), attention=recipe["attention"],
        loss_chunks=recipe["loss_chunks"],
        **{key: model[key] for key in (
            "vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "moe_intermediate_size", "num_experts_per_tok", "norm_topk_prob",
            "rope_theta", "rms_norm_eps", "initializer_range",
            "block_length", "noise_eps", "noise_seed", "mask_token_id")})
    net = sdar.Sdar(cfg)
    tx = sdar.make_optimizer(optax.linear_schedule(
        0.0, recipe["learning_rate"], recipe["lr_warmup_steps"]))
    # a step's positions, both streams, x the experts each takes
    pairs = (2 * traffic["batch"] * traffic["seq"]
             * model["num_experts_per_tok"])
    held, level = model["num_experts"], recipe["selection_bias"]
    mine = slice(shard["index"] * held, (shard["index"] + 1) * held)
    share = pairs / model["num_experts_published"]
    names = [f"layers_{i}" for i in range(model["num_hidden_layers"])]

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
        each receives its uniform share of the two streams of the cell's
        batch under the first step's noise: the update swept over them at a
        falling rate."""
        clean = jax.pure_callback(
            resident_ids, jax.ShapeDtypeStruct(
                (traffic["batch"], traffic["seq"]), jnp.int32),
            jax.random.key_data(key))
        both = jnp.concatenate([sdar.noise(cfg, clean, 0)[0], clean], axis=1)

        def sweep(i, bias):
            load = net.apply({"params": with_bias(params, bias)}, both)[1]
            return moved(bias, load, level["rate"] * level["decay"] ** i)

        return with_bias(params, jax.lax.fori_loop(
            0, level["sweeps"], sweep, jnp.zeros(
                (len(names), model["num_experts_published"]), jnp.float32)))

    rebalanced = jax.jit(lambda rows, load: tuple(moved(
        jnp.stack(rows), load, level["update_rate"])))

    def between(params, out):
        rows = [params[name]["moe"]["router_bias"] for name in names]
        return with_bias(params, rebalanced(rows, jax.tree.leaves(out)[2]))

    def make_state(key):
        params = levelled(sdar.init_params(cfg, key)[1], key)
        return params, tx.init(params)

    def narrow(out):
        # what the step returns besides the loss goes where a user's loop
        # sends it, at the point where the loop reads its loss: the
        # ``train/step_aux`` record of the runtime's ring (``masked_share``,
        # ``rows_present``, the most and the mean rows a held expert got)
        # (loss, {masked_share, tokens_per_expert}), called or compiled
        return sdar.step_metrics(*jax.tree.leaves(out), pairs=pairs)["loss"]

    return types.SimpleNamespace(
        make_state=make_state,
        param_shardings=lambda shapes: sdar.param_shardings(shapes, mesh),
        place_state=lambda params, opt_state: sdar.shard_train_state(
            params, opt_state, mesh),
        step=_Narrowed(sdar.build_train_step(net, tx, donate=True),
                       between, narrow),
        # beyond the contract, for a builder's side run: the loss and its
        # parts at the first step's noise
        loss_with_parts=lambda params, batch: sdar.loss_fn(
            params, net, batch, 0))

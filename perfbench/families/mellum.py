"""The family of the benchmark whose window layers stand three to one over
full layers, both with rotary positions, and whose every feed-forward part
is a softmax-routed expert layer (``mellum``; the contract:
``worker.load_family``).

The program's side is ``ray_tpu.models.mellum``, called as a user calls it:
``init_params``, ``make_optimizer``, ``build_train_step`` (the loss and the
tokens each held expert received), ``step_metrics`` where the loop reads its
loss. The count of parameters and of operations is the benchmark's own, from
the configuration file's keys alone.

The family's keys, beside the ones every configuration shares, are the
published ones (``hidden_size``, ``num_hidden_layers``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``moe_intermediate_size``, ``num_experts_per_tok``, ``norm_topk_prob``,
``sliding_window``, ``layer_types``, ``rope_parameters``, ``rms_norm_eps``)
and the share of the deployment this chip holds: ``num_experts`` is the
number of routed experts HELD in each layer, ``num_experts_published`` the
router's width, ``expert_shard`` ``{index, of}`` which slice they are;
``vocab_size`` is the slice of the vocabulary resident, in the embedding and
in the untied head. ``layer_types`` is the published list, whole; the layers
run are its first ``num_hidden_layers``. ``initializer_range`` is assumed
(the file says so); ``train.attention``, ``train.loss_chunks`` and the
traffic's ``remat`` are the program's options.

The held experts' load is held level, by the recipe and for the reasons of
``perfbench/families/afmoe.py`` (``train.selection_bias``; a recipe of the
benchmark here, as in ``qwen3_next.py``: the published router has no
selection bias, zero is the published router): the state a run starts from
has the held experts' selection bias levelled on the cell's one batch
(``levelled``), after every step the balance update moves it by
``selection_bias.update_rate`` against the load the step reported
(``rebalanced``), and the learning rate climbs linearly over
``train.lr_warmup_steps``. The reference is handed the same bias with the
parameters.

Operations a token (``train_flops_per_token``): a forward and a backward
pass over every weight matrix the token passes through (6 a parameter), the
routed experts by their expectation under uniform routing,
``num_experts_per_tok x held / published`` experts a layer (here two: the
other six a token would use are on other chips); the head once; the
embedding's lookups, the norms' scales and the rotations not at all; and
attention's scores and their use by the pairs each layer's own mask leaves,
exactly, as ``perfbench/families/afmoe.py`` counts them: ``6 H 2 D`` a
pair, ``(T + 1) / 2`` pairs a token in a full layer and ``(W T - W (W - 1) /
2) / T`` in a window layer of ``W`` keys. Recomputed operations do not
count.
"""

from __future__ import annotations

import types

WINDOW, FULL = "sliding_attention", "full_attention"


def layer_types(m: dict) -> tuple:
    """The kinds of the layers run: the published list's first
    ``num_hidden_layers``, held to the published rule (full where
    ``(i + 1) % 4 == 0``)."""
    for i, kind in enumerate(m["layer_types"]):
        if kind != (FULL if (i + 1) % 4 == 0 else WINDOW):
            raise ValueError(f"layer_types[{i}] is not the published rule's")
    return tuple(m["layer_types"][:m["num_hidden_layers"]])


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
            + len(layer_types(m)) * layer)


def matmul_params_per_token(m: dict) -> float:
    """The parameters of the matrices one token is multiplied with, the
    routed experts by their expectation on this chip."""
    s = _sizes(m)
    routed = (m["num_experts_per_tok"] * m["num_experts"]
              / m["num_experts_published"]) * s["expert"]
    return (s["table"]
            + len(layer_types(m)) * (s["attn"] + s["router"] + routed))


def attended_pairs_per_token(m: dict, seq: int) -> float:
    """Query-key pairs a head, a token, summed over the layers run: what
    each layer's own mask leaves of a sequence of ``seq`` tokens."""
    window = min(m["sliding_window"], seq)
    by_kind = {WINDOW: (window * seq - window * (window - 1) / 2) / seq,
               FULL: (seq + 1) / 2}
    return sum(by_kind[kind] for kind in layer_types(m))


def train_flops_per_token(m: dict, seq: int) -> float:
    attention = (6.0 * m["num_attention_heads"] * 2 * m["head_dim"]
                 * attended_pairs_per_token(m, seq))
    return 6.0 * matmul_params_per_token(m) + attention


def build(model: dict, traffic: dict, mesh):
    import jax
    import jax.numpy as jnp
    import optax

    from perfbench import traffic as traffic_mod
    from perfbench.families.afmoe import _Narrowed
    from ray_tpu.models import mellum

    recipe, shard = model["train"], model["expert_shard"]
    if model["num_experts"] * shard["of"] != model["num_experts_published"]:
        raise ValueError("num_experts (held) x expert_shard.of is not "
                         "num_experts_published")
    cfg = mellum.MellumConfig(
        num_experts=model["num_experts_published"],
        expert_shard=(shard["index"], shard["of"]),
        layer_types=layer_types(model),
        dtype=jnp.dtype(recipe["compute_dtype"]),
        remat=bool(traffic.get("remat")), attention=recipe["attention"],
        loss_chunks=recipe["loss_chunks"],
        **{key: model[key] for key in (
            "vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "moe_intermediate_size", "num_experts_per_tok", "norm_topk_prob",
            "sliding_window", "rope_parameters", "rms_norm_eps",
            "initializer_range")})
    net = mellum.Mellum(cfg)
    tx = mellum.make_optimizer(optax.linear_schedule(
        0.0, recipe["learning_rate"], recipe["lr_warmup_steps"]))
    pairs = traffic["batch"] * traffic["seq"] * model["num_experts_per_tok"]
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
        params = levelled(mellum.init_params(cfg, key)[1], key)
        return params, tx.init(params)

    def narrow(out):
        # what the step returns besides the loss goes where a user's loop
        # sends it, at the point where the loop reads its loss: the
        # ``train/step_aux`` record of the runtime's ring (``rows_present``,
        # the most and the mean rows a held expert got)
        loss, tokens = jax.tree.leaves(out)
        return mellum.step_metrics(loss, tokens, pairs=pairs)["loss"]

    return types.SimpleNamespace(
        make_state=make_state,
        param_shardings=lambda shapes: mellum.param_shardings(shapes, mesh),
        place_state=lambda params, opt_state: mellum.shard_train_state(
            params, opt_state, mesh),
        step=_Narrowed(mellum.build_train_step(net, tx, donate=True),
                       between, narrow),
        # beyond the contract, for a builder's side run: the loss and the
        # tokens each held expert received
        loss_with_parts=lambda params, batch: mellum.loss_fn(
            params, net, batch))

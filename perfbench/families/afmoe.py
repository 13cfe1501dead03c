"""The window-and-full-attention, grouped-heads, routed-expert family of the
benchmark (``afmoe``; the contract: ``worker.load_family``).

The program's side is ``ray_tpu.models.afmoe``, called as a user calls it:
``init_params``, ``make_optimizer``, ``build_train_step`` (the loss and the
tokens each held expert received), ``step_metrics`` where the loop reads its
loss. The count of parameters and of operations is the benchmark's own, from
the configuration file's keys alone.

The family's keys, beside the ones every configuration shares, are the
published ones (``hidden_size``, ``num_hidden_layers``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``intermediate_size``, ``moe_intermediate_size``, ``num_dense_layers``,
``num_shared_experts``, ``num_experts_per_tok``, ``route_scale``,
``route_norm``, ``sliding_window``, ``layer_types``, ``rope_theta``,
``rms_norm_eps``) and the share of the deployment this chip holds:
``num_experts`` is the number of routed experts HELD in each expert layer,
``num_experts_published`` the router's width, ``expert_shard`` ``{index,
of}`` which slice they are; ``vocab_size`` is the slice of the vocabulary
resident. ``layer_types`` is the published list, whole; the layers run are
its first ``num_hidden_layers``. ``initializer_range`` is assumed (the file
says so); ``train.attention``, ``train.loss_chunks`` and the traffic's
``remat`` are the program's options.

The held experts' load is held level, as the published recipe holds it
(``load_balance_coeff``): with random weights and a zero selection bias it is
the seed's (27,571 to 43,985 of a step's pairs over eight seeds on the chip,
where the uniform share is 32,768), the router then drifts onto the held
experts, the only ones that lower this chip's loss, and a run's tokens/s told
its seed's routing. So the state a run starts from (``make_state``) has the
held experts' bias levelled on the cell's one batch, as a trained
checkpoint's is on its data (``train.selection_bias``; ``levelled`` below);
after every step the published update moves it by ``load_balance_coeff``
against the load the step reported (``rebalanced``, between steps, as a
user's loop calls it: the bias is no gradient's); and the learning rate
climbs linearly over ``train.lr_warmup_steps``, so that the router moves
more slowly than the update corrects.

Operations a token (``train_flops_per_token``): a forward and a backward
pass over every weight matrix the token passes through (6 a parameter), the
routed experts by their expectation under uniform routing,
``num_experts_per_tok x held / published`` experts a layer (here half an
expert: the other 7.5 a token would use are on other chips); the head once;
the embedding's lookups and the norms' scales not at all; and attention's
scores and their use by the pairs each layer's own mask leaves, exactly:
``6 H (d_qk + d_v)`` a pair, ``(T + 1) / 2`` pairs a token in a full layer
and ``(W T - W (W - 1) / 2) / T`` in a window layer of ``W`` keys. The other
families of this benchmark do not discount the mask (``6 T H (d_qk + d_v)``
a layer, twice a causal layer's pairs), so this cell's ``mfu_pct`` reads
lower than theirs for the same use of the chip; it can never read high.
Recomputed operations do not count.
"""

from __future__ import annotations

import types

WINDOW = "sliding_attention"


def _sizes(m: dict) -> dict:
    d, width = m["hidden_size"], m["moe_intermediate_size"]
    heads, kv, head = (m["num_attention_heads"], m["num_key_value_heads"],
                       m["head_dim"])
    return {
        # q, gate and o over the query heads; k and v over their own
        "attn": 3 * d * heads * head + 2 * d * kv * head,
        "head_norms": 2 * head,
        "block_norms": 4 * d,
        "dense_mlp": 3 * d * m["intermediate_size"],
        "router": d * m["num_experts_published"],
        "router_bias": m["num_experts_published"],
        "expert": 3 * d * width,
        "shared": 3 * d * width * m["num_shared_experts"],
        "dense_layers": m["num_dense_layers"],
        "expert_layers": m["num_hidden_layers"] - m["num_dense_layers"],
        "table": m["vocab_size"] * d,
    }


def num_params(m: dict) -> int:
    s = _sizes(m)
    around = s["attn"] + s["head_norms"] + s["block_norms"]
    expert_layer = (around + s["router"] + s["router_bias"] + s["shared"]
                    + m["num_experts"] * s["expert"])
    return (2 * s["table"] + m["hidden_size"]     # embedding, head, final norm
            + s["dense_layers"] * (around + s["dense_mlp"])
            + s["expert_layers"] * expert_layer)


def matmul_params_per_token(m: dict) -> float:
    """The parameters of the matrices one token is multiplied with, the
    routed experts by their expectation on this chip."""
    s = _sizes(m)
    routed = (m["num_experts_per_tok"] * m["num_experts"]
              / m["num_experts_published"]) * s["expert"]
    return (s["dense_layers"] * (s["attn"] + s["dense_mlp"])
            + s["expert_layers"] * (s["attn"] + s["router"] + s["shared"]
                                    + routed)
            + s["table"])


def layer_types(m: dict) -> tuple:
    """The kinds of the layers run: the published list's first
    ``num_hidden_layers``."""
    return tuple(m["layer_types"][:m["num_hidden_layers"]])


def attended_pairs_per_token(m: dict, seq: int) -> float:
    """Query-key pairs a head, a token, summed over the layers run: what
    each layer's own mask leaves of a sequence of ``seq`` tokens."""
    window = min(m["sliding_window"], seq)
    by_kind = {True: (window * seq - window * (window - 1) / 2) / seq,
               False: (seq + 1) / 2}
    return sum(by_kind[kind == WINDOW] for kind in layer_types(m))


def train_flops_per_token(m: dict, seq: int) -> float:
    attention = (6.0 * m["num_attention_heads"] * 2 * m["head_dim"]
                 * attended_pairs_per_token(m, seq))
    return 6.0 * matmul_params_per_token(m) + attention


class _Narrowed:
    """The program's step, ``(params, opt_state, batch) -> (params,
    opt_state, loss, tokens each held expert received)``, as the worker's
    contract has it: the loss third and last, ``narrow(what follows the
    state) -> loss``, after ``between(params, what follows the state) ->
    params``, what the loop does to the parameters between two steps.
    ``lower`` and ``compile`` hand on what they wrap, wrapped the same way;
    every other name is the wrapped object's."""

    def __init__(self, inner, between, narrow):
        self._inner, self._between, self._narrow = inner, between, narrow

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def lower(self, *args):
        return _Narrowed(self._inner.lower(*args), self._between,
                         self._narrow)

    def compile(self):
        return _Narrowed(self._inner.compile(), self._between, self._narrow)

    def __call__(self, params, opt_state, batch):
        params, opt_state, *out = self._inner(params, opt_state, batch)
        return self._between(params, out), opt_state, self._narrow(out)


def build(model: dict, traffic: dict, mesh):
    import jax
    import jax.numpy as jnp
    import optax

    from perfbench import traffic as traffic_mod
    from ray_tpu.models import afmoe

    recipe, shard = model["train"], model["expert_shard"]
    if model["num_experts"] * shard["of"] != model["num_experts_published"]:
        raise ValueError("num_experts (held) x expert_shard.of is not "
                         "num_experts_published")
    cfg = afmoe.AfmoeConfig(
        num_experts=model["num_experts_published"],
        expert_shard=(shard["index"], shard["of"]),
        layer_types=layer_types(model),
        dtype=jnp.dtype(recipe["compute_dtype"]),
        remat=bool(traffic.get("remat")), attention=recipe["attention"],
        loss_chunks=recipe["loss_chunks"],
        **{key: model[key] for key in (
            "vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "intermediate_size", "moe_intermediate_size", "num_dense_layers",
            "num_shared_experts", "num_experts_per_tok", "route_scale",
            "route_norm", "sliding_window", "global_attn_every_n_layers",
            "rope_theta", "rms_norm_eps", "initializer_range")})
    net = afmoe.Afmoe(cfg)
    tx = afmoe.make_optimizer(optax.linear_schedule(
        0.0, recipe["learning_rate"], recipe["lr_warmup_steps"]))
    pairs = traffic["batch"] * traffic["seq"] * model["num_experts_per_tok"]
    held, level = model["num_experts"], recipe["selection_bias"]
    mine = slice(shard["index"] * held, (shard["index"] + 1) * held)
    share = pairs / model["num_experts_published"]
    expert_layers = [f"layers_{i}" for i in range(
        model["num_dense_layers"], model["num_hidden_layers"])]

    def with_bias(params, bias):
        """``params`` with row i of ``bias`` as expert layer i's selection
        bias."""
        out = dict(params)
        for name, row in zip(expert_layers, bias):
            out[name] = {**out[name],
                         "moe": {**out[name]["moe"], "router_bias": row}}
        return out

    def moved(bias, load, rate):
        """The published balance update, for the held experts' entries of
        ``bias`` [expert layers, experts] (the others' loads are other
        chips' to see): up by ``rate`` under the uniform share of ``load``
        [expert layers, held], down above it."""
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
        jnp.stack(rows), load, model["load_balance_coeff"])))

    def between(params, out):
        rows = [params[name]["moe"]["router_bias"] for name in expert_layers]
        return with_bias(params, rebalanced(rows, jax.tree.leaves(out)[1]))

    def make_state(key):
        params = levelled(afmoe.init_params(cfg, key)[1], key)
        return params, tx.init(params)

    def narrow(out):
        # called, the builder's step hands the loss and the leaves of its
        # auxiliary output on side by side; compiled, as the tree they were.
        # What the step returns besides the loss goes where a user's loop
        # sends it, at the point where the loop reads its loss: the
        # ``train/step_aux`` record of the runtime's ring
        loss, tokens = jax.tree.leaves(out)
        # the loss as the report read it: the worker's own read is then free
        return afmoe.step_metrics(loss, tokens, pairs=pairs)["loss"]

    return types.SimpleNamespace(
        make_state=make_state,
        param_shardings=lambda shapes: afmoe.param_shardings(shapes, mesh),
        place_state=lambda params, opt_state: afmoe.shard_train_state(
            params, opt_state, mesh),
        step=_Narrowed(afmoe.build_train_step(net, tx, donate=True), between,
                       narrow),
        # beyond the contract, for a builder's side run: the loss and the
        # tokens each held expert received
        loss_with_parts=lambda params, batch: afmoe.loss_fn(
            params, net, batch))

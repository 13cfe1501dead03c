"""The family of the benchmark whose every attention layer sees, for each
query, the keys a learned indexer picks, with rotary positions of three
components and image spans in the sequence (``keye``; the contract:
``worker.load_family``).

The program's side is ``ray_tpu.models.keye``, called as a user calls it:
``init_params``, ``make_optimizer``, ``build_train_step`` (the loss, its two
terms and the tokens each held expert received) over a batch that carries
``position_ids`` [3, B, T] and ``loss_weights`` [B, T] beside the worker's
``input_ids`` and ``labels``, ``step_metrics`` where the loop reads its
loss. The count of parameters and of operations is the benchmark's own,
from the configuration file's keys alone.

The family's keys, beside the ones every configuration shares, are the
published ones (``hidden_size``, ``num_hidden_layers``,
``num_attention_heads``, ``num_key_value_heads``, ``head_dim``,
``moe_intermediate_size``, ``num_experts_per_tok``, ``norm_topk_prob``,
``rope_theta``, ``rope_scaling.mrope_section``, ``rms_norm_eps``,
``sa_config``: ``indexer_num_heads``, ``indexer_head_dim``,
``indexer_num_kv_heads`` (1: the program has one index key a token),
``topk``; its chunk sizes change no result) and the share of the deployment
this chip holds: ``num_experts`` is the number of routed experts HELD in
each layer, ``num_experts_published`` the router's width, ``expert_shard``
``{index, of}`` which slice they are; ``vocab_size`` is the slice of the
vocabulary resident, in the embedding and in the untied head.
``initializer_range`` is assumed (the file says so); ``train.attention``,
``train.loss_chunks``, ``train.index_dtype`` and the traffic's ``remat`` are
the program's options.

The sequence's layout is the TRAFFIC's (``images`` spans of ``image_grid``
tokens at ``image_offsets``; ``layout_of``): the positions' three
components by the rule the configuration assumes (the program's
``image_layout``, as a user's loader would call it), and which targets are
text. The ids are the seed's, uniform over the slice at every position: an
embedding row stands where a tower's feature would be merged in. The
reference reads the same file by the path the configuration names
(``reference.layout``); ``build`` refuses a cell whose traffic says
otherwise.

The held experts' load is held level by the recipe and for the reasons of
``perfbench/families/afmoe.py`` (``train.selection_bias``; a recipe of the
benchmark, as in ``sdar.py``: the published router has no selection bias).

Operations a token (``train_flops_per_token``): a forward and a backward
pass over every weight matrix a token passes through (6 a parameter; the
indexer's three projections among them, the routed experts by their
expectation under uniform routing, the head once), and of the attention
what is NEEDED, exactly: the main attention over the selected pairs
(``selected_pairs``: every earlier key while a query has no more than
``topk``, ``topk`` after), ``6 H 2 D`` a pair; the index scores over ALL
causal pairs, forward only (``2 J W`` a pair: the selection is not
differentiated); the KL pass over the selected pairs: every query head's
score again (``2 H D``) and the index scores forward and backward (``6 J
W``). The embedding's lookups, the norms and the rotations not at all;
recomputed operations do not count, nor the threshold search (compares).
"""

from __future__ import annotations

import json
import os
import types

_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
LAYOUT_KEYS = ("images", "image_grid", "image_offsets")


def _sizes(m: dict) -> dict:
    d, heads, kv, head = (m["hidden_size"], m["num_attention_heads"],
                          m["num_key_value_heads"], m["head_dim"])
    sa = m["sa_config"]
    index_heads, width = sa["indexer_num_heads"], sa["indexer_head_dim"]
    return {
        # q and o over the query heads; k and v over their own
        "attn": 2 * d * heads * head + 2 * d * kv * head,
        # index queries, the one index key a token, a weight a head
        "indexer": d * index_heads * width + d * width + d * index_heads,
        "indexer_norm": 2 * width,
        "head_norms": 2 * head,
        "block_norms": 2 * d,
        "router": d * m["num_experts_published"],
        "router_bias": m["num_experts_published"],
        "expert": 3 * d * m["moe_intermediate_size"],
        "table": m["vocab_size"] * d,
    }


def num_params(m: dict) -> int:
    s = _sizes(m)
    layer = (s["attn"] + s["indexer"] + s["indexer_norm"] + s["head_norms"]
             + s["block_norms"] + s["router"] + s["router_bias"]
             + m["num_experts"] * s["expert"])
    return (2 * s["table"] + m["hidden_size"]     # embedding, head, final norm
            + m["num_hidden_layers"] * layer)


def matmul_params_per_token(m: dict) -> float:
    """The parameters of the matrices one token is multiplied with (the
    routed experts by their expectation on this chip)."""
    s = _sizes(m)
    routed = (m["num_experts_per_tok"] * m["num_experts"]
              / m["num_experts_published"]) * s["expert"]
    return (s["table"] + m["num_hidden_layers"]
            * (s["attn"] + s["indexer"] + s["router"] + routed))


def selected_pairs(seq: int, topk: int) -> int:
    """Query-key pairs a sequence that the selection leaves."""
    short = min(seq, topk)
    return short * (short + 1) // 2 + (seq - short) * topk


def attention_flops(m: dict, seq: int) -> dict:
    """Operations one sequence needs of one layer's attention, forward and
    backward, by part."""
    sa = m["sa_config"]
    main = m["num_attention_heads"] * m["head_dim"]
    index = sa["indexer_num_heads"] * sa["indexer_head_dim"]
    chosen, causal = selected_pairs(seq, sa["topk"]), seq * (seq + 1) // 2
    return {"selected": 6.0 * 2 * main * chosen,
            "index_scores": 2.0 * index * causal,
            "index_loss": (2.0 * main + 6.0 * index) * chosen}


def train_flops_per_token(m: dict, seq: int) -> float:
    attention = (m["num_hidden_layers"]
                 * sum(attention_flops(m, seq).values()) / seq)
    return 6.0 * matmul_params_per_token(m) + attention


def layout_of(traffic: dict):
    """(position_ids int32 [3, seq], text_target float32 [seq]) of one
    sequence of the traffic's layout, as a user's loader would make them:
    the program's own rule (``ray_tpu.models.keye.image_layout``; the
    reference has a copy of its own, and ``tests/test_keye.py`` holds the
    two together). A target is text where the NEXT position is no image's
    (the last position's target lies past the layout: text)."""
    import numpy as np

    from ray_tpu.models import keye

    offsets = list(traffic.get("image_offsets", ()))
    if len(offsets) != traffic.get("images", 0):
        raise ValueError("images is not the number of image_offsets")
    ids, image = keye.image_layout(traffic["seq"], offsets,
                                   traffic.get("image_grid", (1, 1)))
    return ids, np.append(~image[1:], True).astype(np.float32)


def _reference_layout(model: dict) -> dict:
    """The layout keys of the traffic file the reference reads."""
    with open(os.path.join(_ROOT, model["reference"]["layout"])) as f:
        said = json.load(f)
    return {key: said.get(key) for key in LAYOUT_KEYS + ("seq",)}


class _Laid:
    """The program's step as the worker's contract has it
    (``afmoe._Narrowed``), over batches that gain the layout's arrays
    (``position_ids``, ``loss_weights``) on their way in: what a user's
    loader would hand the step beside the ids."""

    def __init__(self, inner, extra):
        self._inner, self._extra = inner, extra

    def __getattr__(self, name):
        return getattr(self._inner, name)

    def lower(self, params, opt_state, batch):
        return _Laid(self._inner.lower(params, opt_state,
                                       {**batch, **self._extra}), self._extra)

    def compile(self):
        return _Laid(self._inner.compile(), self._extra)

    def __call__(self, params, opt_state, batch):
        return self._inner(params, opt_state, {**batch, **self._extra})


def build(model: dict, traffic: dict, mesh):
    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from perfbench import traffic as traffic_mod
    from perfbench.families.afmoe import _Narrowed
    from ray_tpu.models import keye

    recipe, shard, sa = model["train"], model["expert_shard"], model["sa_config"]
    if model["num_experts"] * shard["of"] != model["num_experts_published"]:
        raise ValueError("num_experts (held) x expert_shard.of is not "
                         "num_experts_published")
    if sa["indexer_num_kv_heads"] != 1:
        raise ValueError("the program has one index key a token")
    mine = {key: traffic.get(key) for key in LAYOUT_KEYS + ("seq",)}
    if _reference_layout(model) != mine:
        raise ValueError(
            f"the traffic's layout {mine} is not the one the reference "
            f"reads from {model['reference']['layout']}")
    cfg = keye.KeyeConfig(
        num_experts=model["num_experts_published"],
        expert_shard=(shard["index"], shard["of"]),
        mrope_section=tuple(model["rope_scaling"]["mrope_section"]),
        indexer_num_heads=sa["indexer_num_heads"],
        indexer_head_dim=sa["indexer_head_dim"], topk=sa["topk"],
        dtype=jnp.dtype(recipe["compute_dtype"]),
        index_dtype=jnp.dtype(recipe["index_dtype"]),
        remat=bool(traffic.get("remat")), attention=recipe["attention"],
        loss_chunks=recipe["loss_chunks"],
        **{key: model[key] for key in (
            "vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "moe_intermediate_size", "num_experts_per_tok", "norm_topk_prob",
            "rope_theta", "rms_norm_eps", "initializer_range")})
    net = keye.Keye(cfg)
    tx = keye.make_optimizer(optax.linear_schedule(
        0.0, recipe["learning_rate"], recipe["lr_warmup_steps"]))
    rows = traffic["batch"]
    ids, text = layout_of(traffic)
    position_ids = np.ascontiguousarray(
        np.broadcast_to(ids[:, None], (3, rows, traffic["seq"])))
    loss_weights = np.ascontiguousarray(
        np.broadcast_to(text, (rows, traffic["seq"])))
    pairs = rows * traffic["seq"] * model["num_experts_per_tok"]
    held, level = model["num_experts"], recipe["selection_bias"]
    held_slice = slice(shard["index"] * held, (shard["index"] + 1) * held)
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
        [layers, experts]: up by ``rate`` under the uniform share of
        ``load`` [layers, held], down above it."""
        return bias.at[:, held_slice].add(rate * jnp.sign(share - load))

    def resident_ids(key_data):
        # the cell's one batch, as ``run.py`` makes it from ``--seed``: the
        # worker's key is ``PRNGKey(seed)``, whose last word is the seed
        return traffic_mod.resident_tokens(
            int(key_data[-1]), traffic, model["vocab_size"])[:, :-1]

    def levelled(params, key):
        """``params`` with the held experts' selection bias moved until each
        receives its uniform share of the cell's one batch: the update
        swept over it at a falling rate."""
        input_ids = jax.pure_callback(
            resident_ids, jax.ShapeDtypeStruct(
                (rows, traffic["seq"]), jnp.int32), jax.random.key_data(key))

        def sweep(i, bias):
            load = net.apply({"params": with_bias(params, bias)}, input_ids,
                             jnp.asarray(position_ids))[1]
            return moved(bias, load, level["rate"] * level["decay"] ** i)

        return with_bias(params, jax.lax.fori_loop(
            0, level["sweeps"], sweep, jnp.zeros(
                (len(names), model["num_experts_published"]), jnp.float32)))

    rebalanced = jax.jit(lambda rows, load: tuple(moved(
        jnp.stack(rows), load, level["update_rate"])))

    def between(params, out):
        rows = [params[name]["moe"]["router_bias"] for name in names]
        # (loss, {index_loss, lm_loss, tokens_per_expert}): the load last
        return with_bias(params, rebalanced(rows, jax.tree.leaves(out)[3]))

    def make_state(key):
        params = levelled(keye.init_params(cfg, key)[1], key)
        return params, tx.init(params)

    def narrow(out):
        # what the step returns besides the loss goes where a user's loop
        # sends it, at the point where the loop reads its loss: the
        # ``train/step_aux`` record of the runtime's ring
        return keye.step_metrics(*jax.tree.leaves(out), pairs=pairs)["loss"]

    extra = {"position_ids": jax.device_put(position_ids),
             "loss_weights": jax.device_put(loss_weights)}
    return types.SimpleNamespace(
        make_state=make_state,
        param_shardings=lambda shapes: keye.param_shardings(shapes, mesh),
        place_state=lambda params, opt_state: keye.shard_train_state(
            params, opt_state, mesh),
        step=_Laid(_Narrowed(keye.build_train_step(net, tx, donate=True),
                             between, narrow), extra),
        # beyond the contract, for a builder's side run: the loss and its
        # parts, and the layers' selections
        loss_with_parts=lambda params, batch: keye.loss_fn(
            params, net, {**batch, **extra}),
        net=net, extra=extra)

"""The Mamba-2 / attention / un-gated-expert hybrid family of the benchmark
(``nemotron_h``; the contract: ``worker.load_family``).

The program's side is ``ray_tpu.models.nemotron_h``, called as a user calls
it: ``init_params``, ``make_optimizer``, ``build_train_step`` (the loss and
the tokens each held expert received), ``step_metrics`` where the loop reads
its loss. The count of parameters and of operations is the benchmark's own,
from the configuration file's keys alone.

The family's keys, beside the ones every configuration shares, are the
published ones (``hidden_size``, ``hybrid_override_pattern``, ``head_dim``,
``num_attention_heads``, ``num_key_value_heads``, ``mamba_num_heads``,
``mamba_head_dim``, ``ssm_state_size``, ``n_groups``, ``conv_kernel``,
``chunk_size``, the three ``time_step_*``, ``moe_intermediate_size``,
``moe_shared_expert_intermediate_size``, ``num_experts_per_tok``,
``norm_topk_prob``, ``routed_scaling_factor``, ``layer_norm_epsilon``,
``rescale_prenorm_residual``) and the share of the deployment this chip
holds: ``hybrid_override_pattern`` is the published string, whole, and
``kept_layers`` the published indices of the blocks run
(``num_hidden_layers`` of them); ``n_routed_experts`` is the number of
routed experts HELD in each expert block, ``n_routed_experts_published`` the
router's width, ``expert_shard`` ``{index, of}`` which slice they are;
``vocab_size`` is the slice of the vocabulary resident, in the embedding and
in the untied head. ``initializer_range`` is assumed (the file says so);
``train.attention``, ``train.loss_chunks`` and the traffic's ``remat`` are
the program's options.

The held experts' load is held level, by the recipe and for the reasons of
``perfbench/families/afmoe.py`` (``train.selection_bias``): the state a run
starts from has the held experts' selection bias (the published
``e_score_correction_bias``) levelled on the cell's one batch
(``levelled``), after every step the balance update moves it by
``selection_bias.update_rate`` against the load the step reported
(``rebalanced``), and the learning rate climbs linearly over
``train.lr_warmup_steps``. The reference is handed the same bias with the
parameters.

Operations a token (``train_flops_per_token``): a forward and a backward
pass over every weight matrix the token passes through (6 a parameter), the
routed experts by their expectation under uniform routing,
``num_experts_per_tok x held / published`` experts a block; the head once
(the embedding's lookups not at all, nor the norms' scales, the taps, the
decays); attention's scores and their use by the pairs the causal mask
leaves, ``6 H 2 D`` a pair and ``(T + 1) / 2`` pairs a token in each
attention block; in a Mamba block the recurrence at what it needs one
position a step, ``SCAN_OPS`` = 16 an entry of a head's [head_dim, states]
state (5 forward, 11 backward: ``perfbench/metrics/ssd_roofline_pct.py``
says which), whatever the chunked form spends, and the convolution's
``CONV_OPS`` = 33 a channel (the four products, three sums, the bias and the
SiLU forward: 12; 21 backward). Recomputed operations do not count.
"""

from __future__ import annotations

import types

KINDS = {"M": "mamba", "*": "attention", "E": "expert"}
SCAN_OPS = 16
CONV_OPS = 33


def layers_run(m: dict) -> tuple:
    """(published index, kind) of the blocks run."""
    kept = m["kept_layers"]
    if len(kept) != m["num_hidden_layers"]:
        raise ValueError("kept_layers does not name num_hidden_layers blocks")
    pattern = m["hybrid_override_pattern"]
    if len(pattern) != m["published"]["num_hidden_layers"] or set(
            pattern) - set(KINDS):
        raise ValueError("hybrid_override_pattern is not the published one")
    return tuple((i, KINDS[pattern[i]]) for i in kept)


def _sizes(m: dict) -> dict:
    d, heads, kv, dim = (m["hidden_size"], m["num_attention_heads"],
                         m["num_key_value_heads"], m["head_dim"])
    inner = m["mamba_num_heads"] * m["mamba_head_dim"]
    mixed = inner + 2 * m["n_groups"] * m["ssm_state_size"]
    return {
        # z, x, B, C and dt; the out-projection
        "mamba": d * (inner + mixed + m["mamba_num_heads"]) + inner * d,
        # the taps and their bias, A_log, D and dt_bias, the gated norm
        "mamba_rest": ((m["conv_kernel"] + 1) * mixed
                       + 3 * m["mamba_num_heads"] + inner),
        "conv_channels": mixed,
        "scan_entries": inner * m["ssm_state_size"],
        # q and o; k and v over their own heads
        "attention": 2 * d * heads * dim + 2 * d * kv * dim,
        "router": d * m["n_routed_experts_published"],
        "router_bias": m["n_routed_experts_published"],
        "shared": 2 * d * m["moe_shared_expert_intermediate_size"],
        "expert": 2 * d * m["moe_intermediate_size"],
        "table": m["vocab_size"] * d,
    }


def num_params(m: dict) -> int:
    s = _sizes(m)
    block = {"mamba": s["mamba"] + s["mamba_rest"],
             "attention": s["attention"],
             "expert": (s["router"] + s["router_bias"] + s["shared"]
                        + m["n_routed_experts"] * s["expert"])}
    d = m["hidden_size"]
    return (2 * s["table"] + d            # embedding, head, final norm
            + sum(d + block[kind] for _, kind in layers_run(m)))


def matmul_params_per_token(m: dict) -> float:
    """The parameters of the matrices one token is multiplied with, the
    routed experts by their expectation on this chip."""
    s = _sizes(m)
    routed = (m["num_experts_per_tok"] * m["n_routed_experts"]
              / m["n_routed_experts_published"]) * s["expert"]
    block = {"mamba": s["mamba"], "attention": s["attention"],
             "expert": s["router"] + s["shared"] + routed}
    return s["table"] + sum(block[kind] for _, kind in layers_run(m))


def train_flops_per_token(m: dict, seq: int) -> float:
    s = _sizes(m)
    kinds = [kind for _, kind in layers_run(m)]
    attention = (6.0 * m["num_attention_heads"] * 2 * m["head_dim"]
                 * (seq + 1) / 2 * kinds.count("attention"))
    mamba = float((SCAN_OPS * s["scan_entries"]
                   + CONV_OPS * s["conv_channels"]) * kinds.count("mamba"))
    return 6.0 * matmul_params_per_token(m) + attention + mamba


def build(model: dict, traffic: dict, mesh):
    import jax
    import jax.numpy as jnp
    import optax

    from perfbench import traffic as traffic_mod
    from perfbench.families.afmoe import _Narrowed
    from ray_tpu.models import nemotron_h

    recipe, shard = model["train"], model["expert_shard"]
    published = model["n_routed_experts_published"]
    if model["n_routed_experts"] * shard["of"] != published:
        raise ValueError("n_routed_experts (held) x expert_shard.of is not "
                         "n_routed_experts_published")
    layers = layers_run(model)
    cfg = nemotron_h.NemotronHConfig(
        n_routed_experts=published,
        expert_shard=(shard["index"], shard["of"]),
        kept_layers=tuple(model["kept_layers"]),
        dtype=jnp.dtype(recipe["compute_dtype"]),
        remat=bool(traffic.get("remat")), attention=recipe["attention"],
        loss_chunks=recipe["loss_chunks"],
        **{key: model[key] for key in (
            "vocab_size", "hidden_size", "hybrid_override_pattern",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "mamba_num_heads", "mamba_head_dim", "ssm_state_size",
            "n_groups", "conv_kernel", "chunk_size", "time_step_min",
            "time_step_max", "time_step_floor", "moe_intermediate_size",
            "moe_shared_expert_intermediate_size", "num_experts_per_tok",
            "norm_topk_prob", "routed_scaling_factor", "layer_norm_epsilon",
            "rescale_prenorm_residual", "initializer_range")})
    net = nemotron_h.NemotronH(cfg)
    tx = nemotron_h.make_optimizer(optax.linear_schedule(
        0.0, recipe["learning_rate"], recipe["lr_warmup_steps"]))
    pairs = traffic["batch"] * traffic["seq"] * model["num_experts_per_tok"]
    held, level = model["n_routed_experts"], recipe["selection_bias"]
    mine = slice(shard["index"] * held, (shard["index"] + 1) * held)
    share = pairs / published
    names = [f"layers_{i}" for i, kind in layers if kind == "expert"]

    def with_bias(params, bias):
        """``params`` with row i of ``bias`` as expert block i's selection
        bias."""
        out = dict(params)
        for name, row in zip(names, bias):
            out[name] = {**out[name],
                         "mixer": {**out[name]["mixer"], "router_bias": row}}
        return out

    def moved(bias, load, rate):
        """The balance update, for the held experts' entries of ``bias``
        [blocks, experts] (the others' loads are other chips' to see): up by
        ``rate`` under the uniform share of ``load`` [blocks, held], down
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
                (len(names), published), jnp.float32)))

    rebalanced = jax.jit(lambda rows, load: tuple(moved(
        jnp.stack(rows), load, level["update_rate"])))

    def between(params, out):
        rows = [params[name]["mixer"]["router_bias"] for name in names]
        return with_bias(params, rebalanced(rows, jax.tree.leaves(out)[1]))

    def make_state(key):
        params = levelled(nemotron_h.init_params(cfg, key)[1], key)
        return params, tx.init(params)

    def narrow(out):
        # what the step returns besides the loss goes where a user's loop
        # sends it, at the point where the loop reads its loss: the
        # ``train/step_aux`` record of the runtime's ring
        loss, tokens = jax.tree.leaves(out)
        return nemotron_h.step_metrics(loss, tokens, pairs=pairs)["loss"]

    return types.SimpleNamespace(
        make_state=make_state,
        param_shardings=lambda shapes: nemotron_h.param_shardings(
            shapes, mesh),
        place_state=lambda params, opt_state: nemotron_h.shard_train_state(
            params, opt_state, mesh),
        step=_Narrowed(nemotron_h.build_train_step(net, tx, donate=True),
                       between, narrow),
        # beyond the contract, for a builder's side run: the loss and the
        # tokens each held expert received
        loss_with_parts=lambda params, batch: nemotron_h.loss_fn(
            params, net, batch))

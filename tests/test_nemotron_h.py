"""The one-mixer-a-block model of Mamba-2, attention and un-gated expert
blocks (``ray_tpu.models.nemotron_h``), held to the plain reference
``perfbench/families/nemotron_h_reference.py`` at small sizes on the CPU,
seeded weights, no cluster; the un-gated relu^2 experts against a hand
computation; the shares' sum; the grouped gated norm; the pattern string;
its configuration file held to the published widths; the family's count
held to the state the program makes."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import worker
from ray_tpu._private import steptrace
from ray_tpu.models import mla_moe, nemotron_h
from ray_tpu.ops import conv, moe, ssm
from tests.conftest import kernel_calls

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


TOY = _json("perfbench", "tests", "configs", "tiny-nemotron-h.json")
CELL = _json("perfbench", "configs", "nemotron-3-nano-30b-a3b.json")
REFERENCE = worker.load_reference(ROOT, TOY)
FAMILY = worker.load_family(ROOT, TOY)


@functools.lru_cache(maxsize=None)
def _small(items=()):
    config = nemotron_h.NemotronHConfig.small_test(dtype=jnp.float32,
                                                   **dict(items))
    model = nemotron_h.NemotronH(config)
    tokens = np.random.default_rng(1).integers(
        0, config.vocab_size, (2, 257), dtype=np.int32)
    batch = {"input_ids": jnp.asarray(tokens[:, :-1]),
             "labels": jnp.asarray(tokens[:, 1:])}

    @jax.jit
    def init(key):
        # weights large enough that every block moves the output; norms'
        # scales, the decays, D and the biases off their initial values
        params = model.init(key, batch["input_ids"])["params"]
        keys = iter(jax.random.split(jax.random.PRNGKey(5), 200))
        return jax.tree.map(
            lambda p: p * 3.0 if p.ndim >= 2 and p.shape[0] > 4
            else p + 0.3 * jax.random.normal(next(keys), p.shape), params)

    return config, model, init(jax.random.PRNGKey(0)), batch


def _as_reference(config):
    index, of = config.expert_shard
    names = ("hybrid_override_pattern", "num_attention_heads",
             "num_key_value_heads", "head_dim", "mamba_num_heads",
             "mamba_head_dim", "ssm_state_size", "n_groups",
             "num_experts_per_tok", "norm_topk_prob", "routed_scaling_factor",
             "layer_norm_epsilon")
    return {**{name: getattr(config, name) for name in names},
            "kept_layers": list(config.kept_layers),
            "expert_shard": {"index": index, "of": of}}


# one block of each kind (published 2, 3 and 4 of "MEM*EMEM"), a share of
# the experts, and heads and states that the scan's kernels take
_WIDE = (("expert_shard", (1, 2)), ("kept_layers", (2, 3, 4)),
         ("mamba_num_heads", 4), ("mamba_head_dim", 64), ("n_groups", 2),
         ("ssm_state_size", 128), ("chunk_size", 128))


@functools.lru_cache(maxsize=None)
def _reference_answers():
    config, _, params, batch = _small(_WIDE)
    m = _as_reference(config)
    ids, labels = batch["input_ids"], batch["labels"]

    @jax.jit
    def theirs(params):
        loss, grads = jax.value_and_grad(REFERENCE.loss)(
            params, ids, labels, m=m)
        return REFERENCE.hidden_states(params, ids, m=m), loss, grads

    with jax.default_matmul_precision("highest"):
        return theirs(params)


@pytest.mark.parametrize("kernels", [False, True], ids=["twin", "interpret"])
def test_the_model_is_the_reference(kernels, monkeypatch):
    """Hidden states, loss and every gradient of the model against the
    plain reference over one block of each kind, with the scan by its
    chunked twin and by its kernels in interpret mode, the convolutions by
    XLA's form and by their kernels with the bias."""
    if kernels:
        monkeypatch.setattr(ssm, "ssd_auto_impl",
                            lambda x, b: "pallas_interpret")
        monkeypatch.setattr(conv, "causal_auto_impl",
                            lambda x, taps, act: "pallas_interpret")
        jax.clear_caches()
    config, model, params, batch = _small(_WIDE)

    @jax.jit
    def ours(params):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: nemotron_h.loss_fn(p, model, batch),
            has_aux=True)(params)
        return (model.apply({"params": params}, batch["input_ids"])[0], loss,
                aux, grads)

    with jax.default_matmul_precision("highest"):
        hidden, loss, aux, grads = ours(params)
    want, ref_loss, ref_grads = _reference_answers()
    np.testing.assert_allclose(hidden, want, rtol=2e-4, atol=2e-4)
    assert aux["tokens_per_expert"].shape == (1, 4)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(ref_grads)):
        few = g.size <= 4        # sums over every position that nearly cancel
        np.testing.assert_allclose(
            g, r, rtol=2e-2 if few else 2e-3,
            atol=3e-4 * float(jnp.abs(r).max()) + 1e-9,
            err_msg=jax.tree_util.keystr(path))
    # the selection bias takes no gradient; the head is untied
    assert not np.asarray(grads["layers_4"]["mixer"]["router_bias"]).any()
    assert params["lm_head"].shape == params["embed"]["embedding"].shape
    if kernels:
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda p: nemotron_h.loss_fn(p, model, batch)[0]))(params)
        assert kernel_calls(jaxpr) == {
            "ssd_fwd": 1, "ssd_bwd": 1, "causal_conv_fwd": 3,
            "causal_conv_bwd": 3}
    jax.clear_caches()


def test_the_pattern_string_names_the_kinds_and_the_kept_blocks_leave_their_record():
    kinds = nemotron_h.layer_kinds(CELL["hybrid_override_pattern"])
    assert len(kinds) == 52
    assert [kinds.count(k) for k in ("mamba", "expert", "attention")] == [
        23, 23, 6]
    assert kinds[:9] == ("mamba", "expert", "mamba", "expert", "mamba",
                         "attention", "expert", "mamba", "expert")
    with pytest.raises(AssertionError):
        nemotron_h.layer_kinds("ME-M")
    config = nemotron_h.NemotronHConfig.small_test(
        kept_layers=(1, 3, 5), dtype=jnp.float32)
    assert config.layers == ((1, "expert"), (3, "attention"), (5, "mamba"))
    assert config.num_hidden_layers == 8
    model = nemotron_h.NemotronH(config)
    ids = jnp.zeros((1, 32), jnp.int32)
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        params = model.init(jax.random.PRNGKey(0), ids)["params"]
        records = [r["values"] for r in steptrace.snapshot()
                   if r["kind"] == "counters"
                   and r["name"] == "model/layer_kinds"]
    finally:
        steptrace.set_enabled(False)
    assert records[-1] == {"mamba": 1, "attention": 1, "expert": 1,
                           "layers": 3, "published_layers": 8}
    # parameters are named by the published index; one mixer a block
    assert set(params) == {"embed", "lm_head", "norm", "layers_1",
                           "layers_3", "layers_5"}
    assert set(params["layers_5"]) == {"norm", "mixer"}
    assert set(params["layers_5"]["mixer"]) == {
        "in_proj", "conv_weight", "conv_bias", "A_log", "D", "dt_bias",
        "norm", "out_proj"}
    assert params["layers_5"]["mixer"]["in_proj"].shape == (
        64, 64 + (64 + 2 * 32) + 4)
    np.testing.assert_allclose(params["layers_5"]["mixer"]["A_log"],
                               np.log([1, 2, 3, 4]), rtol=1e-6)
    step = np.asarray(jax.nn.softplus(params["layers_5"]["mixer"]["dt_bias"]))
    assert (step >= 0.001 - 1e-6).all() and (step <= 0.1 + 1e-6).all()
    assert np.abs(params["layers_5"]["mixer"]["conv_weight"]).max() <= 0.5
    # the Mamba out-projection alone is scaled down by sqrt(published blocks)
    out = np.asarray(params["layers_5"]["mixer"]["out_proj"]["kernel"]).std()
    assert out == pytest.approx(0.02 / 8 ** 0.5, rel=0.15)
    assert np.asarray(params["layers_3"]["mixer"]["o_proj"]["kernel"]
                      ).std() == pytest.approx(0.02, rel=0.15)


def _expert_layer(index, of, activation="relu2"):
    return mla_moe.RoutedExperts(
        experts=8, expert_shard=(index, of), width=16, per_token=3,
        scale=2.5, normalize=True, shared=2, dtype=jnp.float32,
        kernel_init=jax.nn.initializers.normal(0.3), eps=1e-20,
        score="sigmoid", activation=activation)


_M = {"num_experts_per_tok": 3, "norm_topk_prob": True,
      "routed_scaling_factor": 2.5}


def test_relu2_experts_against_a_hand_computation():
    """``held_expert_ffn(activation="relu2")``: ``wi`` [held, d, width],
    ``relu(x wi)^2 wo`` weighted and summed over a token's pairs on held
    experts, and the gradients of x, the weights and both matrices, against
    the same written densely over every pair."""
    keys = jax.random.split(jax.random.PRNGKey(0), 6)
    tokens, d, width, held, k = 40, 32, 16, 4, 3
    x = jax.random.normal(keys[0], (tokens, d))
    wi = 0.3 * jax.random.normal(keys[1], (held, d, width))
    wo = 0.3 * jax.random.normal(keys[2], (held, width, d))
    # a token's k experts differ; experts 4 .. 7 are held (index 1 of 2)
    experts = jnp.argsort(jax.random.uniform(keys[3], (tokens, 8)))[:, :k]
    weights = jax.random.uniform(keys[4], (tokens, k))
    w = jax.random.normal(keys[5], (tokens, d))

    def ours(x, weights, wi, wo):
        y, n = moe.held_expert_ffn(x, experts.astype(jnp.int32), weights, wi,
                                   wo, index=1, of=2, activation="relu2")
        return (y * w).sum(), n

    def dense(x, weights, wi, wo):
        up = jax.nn.relu(jnp.einsum("td,edh->teh", x, wi))
        each = jnp.einsum("teh,ehd->ted", up * up, wo)       # [T, held, d]
        chosen = (experts[:, :, None] - 4 == jnp.arange(held)).astype(
            x.dtype)                                         # [T, k, held]
        per_expert = jnp.einsum("tk,tke->te", weights, chosen)
        return (jnp.einsum("te,ted->td", per_expert, each) * w).sum()

    with jax.default_matmul_precision("highest"):
        (got, n), g = jax.value_and_grad(ours, argnums=(0, 1, 2, 3),
                                         has_aux=True)(x, weights, wi, wo)
        want, r = jax.value_and_grad(dense, argnums=(0, 1, 2, 3))(
            x, weights, wi, wo)
    np.testing.assert_allclose(got, want, rtol=1e-4)
    assert int(n.sum()) == int(((experts >= 4)).sum())
    mine = np.asarray(experts >= 4)
    for name, a, b in zip(("dx", "dweights", "dwi", "dwo"), g, r):
        if name == "dweights":      # a pair of an absent expert has none
            assert not np.asarray(a)[~mine].any()
            a, b = np.asarray(a)[mine], np.asarray(b)[mine]
        np.testing.assert_allclose(a, b, rtol=2e-4,
                                   atol=2e-5 * float(np.abs(b).max()),
                                   err_msg=name)
    with pytest.raises(AssertionError):
        moe.held_expert_ffn(x, experts.astype(jnp.int32), weights, wi, wo,
                            index=1, of=2, activation="gelu")


def test_the_layers_activation_is_a_field():
    """``swiglu`` (the default) is the four older families' layer: ``wi``
    twice the width and a gated shared expert; ``relu2`` has ``wi`` of the
    width and a shared expert of the same un-gated form."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32))
    gated = _expert_layer(0, 2, "swiglu").init(jax.random.PRNGKey(1), x)[
        "params"]
    plain = _expert_layer(0, 2).init(jax.random.PRNGKey(1), x)["params"]
    assert gated["experts_wi"].shape == (4, 32, 32)
    assert plain["experts_wi"].shape == (4, 32, 16)
    assert set(gated["shared_experts"]) == {"gate_proj", "up_proj",
                                            "down_proj"}
    assert set(plain["shared_experts"]) == {"up_proj", "down_proj"}
    assert plain["shared_experts"]["up_proj"]["kernel"].shape == (32, 32)
    with jax.default_matmul_precision("highest"):
        y, n = _expert_layer(0, 2).apply({"params": plain}, x)
        np.testing.assert_allclose(
            y, REFERENCE._experts(x, plain, {
                **_M, "expert_shard": {"index": 0, "of": 2}}),
            rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("of", [2, 4, 8, 16])
def test_the_shares_add_up_to_the_uncut_layer(of):
    """The routed parts of all ``of`` shares (16 experts: the cell's 16-way
    share at one expert a chip), and the shared expert that every chip
    computes alike counted once, are the uncut reference's layer; each share
    is the reference's share; every pair fell on exactly one share."""
    keys = jax.random.split(jax.random.PRNGKey(4), 2)
    x = jax.random.normal(keys[0], (2, 48, 32))
    layer = lambda i, n: mla_moe.RoutedExperts(
        experts=16, expert_shard=(i, n), width=16, per_token=3, scale=2.5,
        normalize=True, shared=2, dtype=jnp.float32,
        kernel_init=jax.nn.initializers.normal(0.3), eps=1e-20,
        score="sigmoid", activation="relu2")
    whole = layer(0, 1).init(keys[1], x)["params"]
    whole["router_bias"] = 0.1 * jax.random.normal(keys[0], (16,))

    def share(index):
        held = 16 // of
        rows = slice(index * held, (index + 1) * held)
        return {**whole, "experts_wi": whole["experts_wi"][rows],
                "experts_wo": whole["experts_wo"][rows]}

    with jax.default_matmul_precision("highest"):
        parts = [layer(i, of).apply({"params": share(i)}, x)
                 for i in range(of)]
        shared = REFERENCE._relu2(x, whole["shared_experts"])
        uncut = REFERENCE._experts(
            x, whole, {**_M, "expert_shard": {"index": 0, "of": 1}})
        np.testing.assert_allclose(
            sum(y for y, _ in parts) - (of - 1) * shared, uncut,
            rtol=2e-4, atol=2e-5)
        assert sum(int(n.sum()) for _, n in parts) == 2 * 48 * 3
        for i in (0, of - 1):
            np.testing.assert_allclose(
                parts[i][0], REFERENCE._experts(
                    x, share(i),
                    {**_M, "expert_shard": {"index": i, "of": of}}),
                rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("shape,impl", [((4, 32), None),
                                        ((2, 32, 512), "pallas_interpret")],
                         ids=["twin", "kernels"])
def test_the_grouped_gated_norm(monkeypatch, shape, impl):
    """``y * silu(z)`` first, then ``/ rms`` over each group of channels,
    one weight a channel: a group's scale does not move another's. Through
    ``ops.norm``'s twin, and through its kernel pair (interpreted) at a
    shape they take."""
    if impl:
        monkeypatch.setattr(nemotron_h, "gated_group_rms_norm",
                            functools.partial(
                                nemotron_h.gated_group_rms_norm, impl=impl))
    width, run = shape[-1], shape[-1] // 4
    y = 3.0 * jax.random.normal(jax.random.PRNGKey(0), shape)
    z = jax.random.normal(jax.random.PRNGKey(1), shape)
    norm = nemotron_h.GroupRMSNorm(4, 1e-5, jnp.float32)
    params = norm.init(jax.random.PRNGKey(2), y, z)["params"]
    np.testing.assert_array_equal(params["scale"], np.ones(width))
    scale = 1.0 + 0.1 * np.arange(width, dtype=np.float32) * 32 / width
    got = norm.apply({"params": {"scale": scale}}, y, z)
    gated = np.asarray(y, np.float64) * np.asarray(jax.nn.silu(z), np.float64)
    groups = gated.reshape(*shape[:-1], 4, run)
    want = (groups / np.sqrt((groups ** 2).mean(-1, keepdims=True) + 1e-5)
            ).reshape(shape) * scale
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    louder = y.at[..., :run].multiply(100.0)
    np.testing.assert_allclose(
        norm.apply({"params": {"scale": scale}}, louder, z)[..., run:],
        got[..., run:], rtol=1e-5, atol=1e-6)
    # ungated-then-normed would differ: the gate is inside the statistics
    other = (np.asarray(y) / np.sqrt(
        (np.asarray(y).reshape(*shape[:-1], 4, run) ** 2
         ).mean(-1, keepdims=True) + 1e-5).repeat(run, -1).reshape(shape)
             ) * np.asarray(jax.nn.silu(z)) * scale
    assert np.abs(other - want).max() > 0.1


def test_the_configuration_holds_the_published_widths():
    """Every number of the catalog row's ``config`` stands in the file under
    its key, but the three in ``reduced``; the deployment and what is assumed
    are said."""
    rows = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")] \
        if os.path.exists(
            "/opt/skills/guides/model-configs/architectures.jsonl") else []
    published = {
        "hidden_size": 2688, "mamba_num_heads": 64, "mamba_head_dim": 64,
        "ssm_state_size": 128, "n_groups": 8, "conv_kernel": 4,
        "chunk_size": 128, "num_attention_heads": 32,
        "num_key_value_heads": 2, "head_dim": 128, "num_experts_per_tok": 6,
        "routed_scaling_factor": 2.5, "moe_intermediate_size": 1856,
        "moe_shared_expert_intermediate_size": 3712, "n_shared_experts": 1,
        "layer_norm_epsilon": 1e-5, "use_conv_bias": True,
        "mlp_hidden_act": "relu2", "norm_topk_prob": True,
        "hybrid_override_pattern":
            "MEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEM*EMEMEMEM*EMEMEMEME"}
    for key, value in published.items():
        assert CELL[key] == value, key
    reduced = {"num_hidden_layers": (52, 9), "n_routed_experts": (128, 8),
               "vocab_size": (131072, 16384)}
    for key, (was, now) in reduced.items():
        assert CELL["published"][key] == was and CELL[key] == now
        assert key in CELL["reduced_note"]
    assert CELL["n_routed_experts_published"] == 128
    assert CELL["kept_layers"] == list(range(9))
    assert CELL["expert_shard"] == {"index": 0, "of": 16}
    for row in rows:
        if row["name"] == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16":
            assert CELL["source"] == row["source_url"]
            for key, value in row["config"].items():
                assert key in CELL, key
                if key not in reduced:
                    assert CELL[key] == value, key
    bench = _json("BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "nemotron-3-nano-30b-a3b")
    assert entry["reduced"] == list(reduced)
    for key in ("attention", "mamba", "mamba_init", "experts",
                "rescale_prenorm_residual", "router_bias",
                "auxiliary_balance_loss", "multi_token_prediction"):
        assert key in CELL["assumed"], key
    assert "16 v5e chips" in CELL["deployment"]


def test_the_familys_count_is_the_state_the_program_makes():
    """``num_params`` from the file's keys alone against the parameters the
    program initialises, for the toy and (by shape) for the cell."""
    family = worker.load_family(ROOT, CELL)
    assert family.num_params(CELL) == 666_963_456
    for model, traffic in ((TOY, {"batch": 4, "seq": 64, "remat": True}),
                           (CELL, {"batch": 2, "seq": 8192, "remat": True})):
        built = family.build(model, traffic, None)
        shapes = jax.eval_shape(built.make_state, jax.random.PRNGKey(0))[0]
        assert sum(x.size for x in jax.tree.leaves(shapes)) \
            == family.num_params(model)

"""The short-convolution, grouped-attention, routed-expert model
(``ray_tpu.models.lfm2``), held to the plain reference
``perfbench/families/lfm2_reference.py`` at small sizes on the CPU, seeded
weights, no cluster; the expert layer with no shared expert and the shares'
sum; its configuration file held to the published widths; the benchmark
family's step as the worker calls it."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import compare, worker
from ray_tpu._private import steptrace
from ray_tpu.models import lfm2, mla_moe
from ray_tpu.ops import conv, moe
from tests.conftest import kernel_calls

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


TOY = _json("perfbench", "tests", "configs", "tiny-lfm2.json")
CELL = _json("perfbench", "configs", "lfm2-8b-a1b.json")
REFERENCE = worker.load_reference(ROOT, TOY)
FAMILY = worker.load_family(ROOT, TOY)
TRAFFIC = {"batch": 4, "seq": 64, "remat": True}


def _tokens(seed, vocab=TOY["vocab_size"], batch=4, seq=64):
    return np.random.default_rng(seed).integers(
        0, vocab, (batch, seq + 1), dtype=np.int32)


def _differences(dtype, seed=3, round_weights=False):
    """The comparison the benchmark's worker makes, in small: the step's
    loss and its gradient (from Adam's first moment) against the float32
    reference -> (loss, gradient norm: relative; cosine)."""
    model = dict(TOY, train=dict(TOY["train"], compute_dtype=dtype))
    built = FAMILY.build(model, TRAFFIC, None)
    params, opt_state = jax.jit(built.make_state)(jax.random.PRNGKey(seed))
    tokens = _tokens(seed)
    ref_loss, ref_grads = REFERENCE.over_microbatches(
        model, params, tokens, 2, True, jnp.asarray)
    if round_weights:
        # the control: weights kept to 3 bits of mantissa
        def chop(x):
            if x.ndim < 2:
                return x
            m, e = jnp.frexp(x)
            return jnp.ldexp(jnp.round(m * 16) / 16, e)

        params = jax.tree.map(chop, params)
    batch = {"input_ids": jnp.asarray(tokens[:, :-1]),
             "labels": jnp.asarray(tokens[:, 1:])}
    _, opt_state, loss = built.step(params, opt_state, batch)
    ns, nr, cos = (float(v) for v in compare.compare_gradients(
        compare.system_gradient(opt_state, 0.9), ref_grads))
    return (abs(float(loss) - float(ref_loss)) / float(ref_loss),
            abs(ns - nr) / nr, cos)


def test_float32_step_is_the_reference_to_rounding():
    """The toy configuration's limits (float32 on both sides)."""
    loss, norm, cos = _differences("float32")
    limits = TOY["reference"]
    assert loss <= limits["loss_rel_tol"]
    assert norm <= limits["grad_norm_rel_tol"]
    assert cos >= limits["grad_cosine_min"]


def test_a_step_in_a_lower_precision_is_outside_the_toy_limits():
    """bfloat16 compute, and weights kept to 3 bits of mantissa: each past
    the float32 limit on the gradient's cosine."""
    assert _differences("bfloat16")[2] < TOY["reference"]["grad_cosine_min"]
    assert _differences("float32", round_weights=True)[2] < 0.999


def _small(**kw):
    config = lfm2.Lfm2Config.small_test(dtype=jnp.float32, **kw)
    model, params = lfm2.init_params(config, jax.random.PRNGKey(0))
    # weights large enough that every layer moves the output
    params = jax.tree.map(
        lambda p: p * 3.0 if p.ndim >= 2 else p, params)
    tokens = _tokens(1, vocab=config.vocab_size, batch=2)
    batch = {"input_ids": jnp.asarray(tokens[:, :-1]),
             "labels": jnp.asarray(tokens[:, 1:])}
    return config, model, params, batch


def _interpreted(bcx, taps):
    """``conv.auto_impl`` as on a TPU, the kernels in interpret mode."""
    return "pallas_interpret" if conv.fits(bcx, taps) else "jnp"


def _as_reference(config):
    index, of = config.expert_shard
    return {"layer_types": list(config.layer_types),
            "kept_layers": list(config.kept_layers),
            "num_dense_layers": config.num_dense_layers,
            "num_attention_heads": config.num_attention_heads,
            "num_key_value_heads": config.num_key_value_heads,
            "norm_eps": config.norm_eps, "rope_theta": config.rope_theta,
            "num_experts_per_tok": config.num_experts_per_tok,
            "norm_topk_prob": config.norm_topk_prob,
            "routed_scaling_factor": config.routed_scaling_factor,
            "route_eps": config.route_eps, "expert_shard": {"index": index, "of": of}}


@pytest.mark.parametrize("kernels", [False, True], ids=["jnp", "interpret"])
def test_the_model_is_the_reference(kernels, monkeypatch):
    """Logits, loss and every gradient of the model against the plain
    reference, with the convolution by its ``jnp`` form and by its kernels
    in interpret mode; a share of the experts (1 of 2)."""
    if kernels:
        monkeypatch.setattr(conv, "auto_impl", _interpreted)
        jax.clear_caches()
    config, model, params, batch = _small(expert_shard=(1, 2))
    m = _as_reference(config)
    with jax.default_matmul_precision("highest"):
        hidden, tokens = model.apply({"params": params}, batch["input_ids"])
        want = REFERENCE.hidden_states(params, batch["input_ids"], m=m)
        np.testing.assert_allclose(hidden, want, rtol=2e-4, atol=2e-4)
        (loss, aux), grads = jax.value_and_grad(
            lambda p: lfm2.loss_fn(p, model, batch), has_aux=True)(params)
        ref_loss, ref_grads = jax.value_and_grad(REFERENCE.loss)(
            params, batch["input_ids"], batch["labels"], m=m)
    assert tokens.shape == aux["tokens_per_expert"].shape == (4, 4)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(ref_grads)):
        np.testing.assert_allclose(
            g, r, rtol=2e-3, atol=2e-4 * float(jnp.abs(r).max()) + 1e-9,
            err_msg=jax.tree_util.keystr(path))
    # the selection bias takes no gradient; the tied table takes the head's
    # and the embedding's
    assert not np.asarray(grads["layers_2"]["moe"]["router_bias"]).any()
    assert "lm_head" not in params
    jax.clear_caches()


def test_the_layers_run_are_the_kept_ones_by_their_published_index():
    config, _, params, _ = _small()
    assert config.layers == ((0, "conv", True), (2, "full_attention", False),
                             (3, "conv", False), (4, "conv", False),
                             (5, "conv", False))
    assert sorted(k for k in params if k.startswith("layers_")) == [
        "layers_0", "layers_2", "layers_3", "layers_4", "layers_5"]
    assert set(params["layers_0"]) == {"operator_norm", "conv", "ffn_norm",
                                       "mlp"}
    assert set(params["layers_2"]) == {"operator_norm", "attn", "ffn_norm",
                                       "moe"}
    assert set(params["layers_3"]["conv"]) == {"in_proj", "conv_weight",
                                               "out_proj"}
    assert params["layers_3"]["conv"]["conv_weight"].shape == (3, 128)
    assert params["layers_3"]["conv"]["in_proj"]["kernel"].shape == (128, 384)
    # the published stack: 18 convolution layers, 6 attention layers
    whole = lfm2.Lfm2Config()
    assert len(whole.layers) == 24 and [
        i for i, kind, _ in whole.layers if kind == "full_attention"] == [
        2, 6, 10, 14, 18, 21]
    assert [dense for _, _, dense in whole.layers] == [True] * 2 + [False] * 22


def test_a_traced_model_records_its_layer_kinds_and_its_convolutions():
    config, model, params, batch = _small(remat=True)
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        jax.clear_caches()
        jax.make_jaxpr(jax.grad(
            lambda p: lfm2.loss_fn(p, model, batch)[0]))(params)
        records = [r for r in steptrace.snapshot() if r["kind"] == "counters"]
    finally:
        steptrace.set_enabled(False)
        jax.clear_caches()
    kinds = [r["values"] for r in records if r["name"] == "model/layer_kinds"]
    assert kinds and kinds[-1] == {
        "conv": 4, "full_attention": 1, "dense": 1, "expert": 4, "layers": 5,
        "published_layers": 6}
    # on the CPU the convolution is the jnp form: no kernel, no record
    assert not [r for r in records if r["name"] == "conv/short"]


def test_recomputation_changes_no_value_and_runs_the_convolution_again(
        monkeypatch):
    """``remat`` changes no loss and no gradient. With the kernels (interpret
    mode) a recomputed convolution block runs the forward kernel twice and
    the backward once: nothing of the convolution is kept."""
    monkeypatch.setattr(conv, "auto_impl", _interpreted)
    jax.clear_caches()
    config, model, params, batch = _small(expert_shard=(0, 2))
    again = lfm2.Lfm2(dataclasses.replace(config, remat=True))
    grad = lambda net: jax.value_and_grad(
        lambda p: lfm2.loss_fn(p, net, batch)[0])
    (loss, grads), (loss2, grads2) = grad(model)(params), grad(again)(params)
    np.testing.assert_allclose(loss, loss2, rtol=1e-6)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads2)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
    calls = lambda net: kernel_calls(jax.make_jaxpr(grad(net))(params))
    assert calls(model)["short_conv_fwd"] == 4
    assert calls(again)["short_conv_fwd"] == 8
    assert calls(model)["short_conv_bwd"] == calls(again)[
        "short_conv_bwd"] == 4
    jax.clear_caches()


# ----------------------------------------------------------------------
# the expert layer: no shared expert, the family's eps, the shares' sum
# ----------------------------------------------------------------------

def _expert_layer(index, of, shared=0, eps=1e-6):
    return mla_moe.RoutedExperts(
        experts=8, expert_shard=(index, of), width=16, per_token=3,
        scale=1.0, normalize=True, shared=shared, dtype=jnp.float32,
        kernel_init=jax.nn.initializers.normal(0.5), eps=eps)


def test_no_shared_expert_builds_no_parameter():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32))
    none = _expert_layer(0, 2).init(jax.random.PRNGKey(1), x)["params"]
    one = _expert_layer(0, 2, shared=1).init(jax.random.PRNGKey(1),
                                             x)["params"]
    assert set(none) == {"router", "router_bias", "experts_wi", "experts_wo"}
    assert set(one) == set(none) | {"shared_experts"}
    assert one["shared_experts"]["gate_proj"]["kernel"].shape == (32, 16)
    # the routed part is the same function of the same parameters
    y0, n0 = _expert_layer(0, 2).apply({"params": none}, x)
    y1, n1 = _expert_layer(0, 2, shared=1).apply({"params": one}, x)
    np.testing.assert_array_equal(n0, n1)
    with jax.default_matmul_precision("highest"):
        shared = REFERENCE._swiglu(x, one["shared_experts"])
    np.testing.assert_allclose(y1 - y0, shared, rtol=1e-4, atol=1e-5)


def test_eps_reaches_the_weights():
    """``topk_routing``'s term under the chosen scores' sum is the caller's:
    the weights of a token sum to ``S / (S + eps)``, and the default is the
    two older families' 1e-20."""
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    router = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    bias = jnp.zeros((8,))
    _, plain = moe.topk_routing(x, router, bias, 3)
    _, old = moe.topk_routing(x, router, bias, 3, eps=1e-20)
    _, wide = moe.topk_routing(x, router, bias, 3, eps=0.5)
    np.testing.assert_array_equal(plain, old)
    np.testing.assert_allclose(plain.sum(-1), 1.0, rtol=1e-6)
    scores = jax.nn.sigmoid(jnp.dot(x, router, precision="highest"))
    chosen = jnp.sort(scores, axis=-1)[:, -3:].sum(-1)
    np.testing.assert_allclose(wide.sum(-1), chosen / (chosen + 0.5),
                               rtol=1e-5)
    # through the layer: the model's eps against the default's
    y = jax.random.normal(jax.random.PRNGKey(2), (2, 16, 32))
    params = _expert_layer(0, 1).init(jax.random.PRNGKey(3), y)["params"]
    a, _ = _expert_layer(0, 1, eps=1e-6).apply({"params": params}, y)
    b, _ = _expert_layer(0, 1, eps=0.5).apply({"params": params}, y)
    assert float(jnp.abs(a - b).max()) > 1e-3 * float(jnp.abs(a).max())


@pytest.mark.parametrize("of", [2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(of):
    """The routed parts of all ``of`` shares are the uncut reference's
    layer (there is nothing every chip computes alike to count once: no
    shared expert); each share is the reference's share; every pair fell on
    exactly one share."""
    keys = jax.random.split(jax.random.PRNGKey(4), 2)
    x = jax.random.normal(keys[0], (2, 48, 32))
    whole = _expert_layer(0, 1).init(keys[1], x)["params"]
    m = {"num_experts_per_tok": 3, "norm_topk_prob": True,
         "routed_scaling_factor": 1.0, "route_eps": 1e-6}

    def share(index):
        held = 8 // of
        rows = slice(index * held, (index + 1) * held)
        return {**whole, "experts_wi": whole["experts_wi"][rows],
                "experts_wo": whole["experts_wo"][rows]}

    with jax.default_matmul_precision("highest"):
        parts = [_expert_layer(i, of).apply({"params": share(i)}, x)
                 for i in range(of)]
        uncut = REFERENCE._experts(
            x, whole, {**m, "expert_shard": {"index": 0, "of": 1}})
        np.testing.assert_allclose(sum(y for y, _ in parts), uncut,
                                   rtol=2e-4, atol=2e-5)
        assert sum(int(n.sum()) for _, n in parts) == 2 * 48 * 3
        for i in (0, of - 1):
            np.testing.assert_allclose(
                parts[i][0], REFERENCE._experts(
                    x, share(i),
                    {**m, "expert_shard": {"index": i, "of": of}}),
                rtol=2e-4, atol=2e-5)


# ----------------------------------------------------------------------
# the step, alone and as the benchmark's family hands it to the worker
# ----------------------------------------------------------------------

def test_the_step_is_the_one_builder_and_reports_the_experts_load():
    """``lfm2.build_train_step`` is ``parallel.build_train_step`` with the
    auxiliary output; the selection bias stays where it was initialised; a
    loop's report carries the loss and the experts' load, and the step
    observatory gets them as one ``train/step_aux`` record."""
    config, model, params, batch = _small(expert_shard=(0, 2))
    tx = lfm2.make_optimizer()
    pairs = batch["input_ids"].size * config.num_experts_per_tok
    step = lfm2.build_train_step(model, tx, donate=False)
    new_params, _, loss, tokens = step(params, tx.init(params), batch)
    assert tokens.shape == (4, 4)
    assert not np.asarray(
        new_params["layers_2"]["moe"]["router_bias"]).any()
    assert np.asarray(new_params["layers_3"]["conv"]["conv_weight"] !=
                      params["layers_3"]["conv"]["conv_weight"]).any()
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        metrics = lfm2.step_metrics(loss, tokens, pairs=pairs)
        records = [r for r in steptrace.snapshot()
                   if r["kind"] == "counters"]
    finally:
        steptrace.set_enabled(False)
    assert set(metrics) == {"loss", "expert_tokens_max", "expert_tokens_mean",
                            "rows_present", "rows_buffered", "rows_fill"}
    assert metrics["rows_present"] == int(np.asarray(tokens).sum())
    assert len(records) == 1 and records[0]["name"] == "train/step_aux"
    assert records[0]["values"] == metrics


def test_the_familys_step_is_the_workers_and_fills_the_ring():
    """The benchmark's family hands the worker a step that returns the loss
    third and last, lowered and compiled as the worker lowers and compiles
    it; every call leaves one ``train/step_aux`` record. After each step the
    held experts' entries of every expert layer's selection bias have moved
    by ``selection_bias.update_rate`` against the load that step reported;
    the other experts' entries stay zero."""
    built = FAMILY.build(TOY, TRAFFIC, None)
    params, opt_state = jax.jit(built.make_state)(jax.random.PRNGKey(0))
    tokens = _tokens(0)
    batch = {"input_ids": jnp.asarray(tokens[:, :-1]),
             "labels": jnp.asarray(tokens[:, 1:])}
    compiled = built.step.lower(params, opt_state, batch).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0
    held, index = TOY["num_experts"], TOY["expert_shard"]["index"]
    mine = slice(index * held, (index + 1) * held)
    share = 4 * 64 * TOY["num_experts_per_tok"] / TOY["num_experts_published"]

    def biases(tree):
        return np.stack([np.asarray(tree[f"layers_{i}"]["moe"]["router_bias"])
                         for i in (2, 3, 4, 5)])

    load = np.asarray(built.loss_with_parts(params, batch)[1][
        "tokens_per_expert"])
    before = biases(params)
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        out = compiled(params, opt_state, batch)
        after = biases(out[0])
        out = built.step(*out[:2], batch)
        records = [r["values"] for r in steptrace.snapshot()
                   if r["kind"] == "counters"
                   and r["name"] == "train/step_aux"]
    finally:
        steptrace.set_enabled(False)
    np.testing.assert_allclose(
        after[:, mine] - before[:, mine],
        TOY["train"]["selection_bias"]["update_rate"]
        * np.sign(share - load), atol=1e-7)
    after[:, mine] = 0
    assert not after.any()
    assert len(out) == 3 and np.ndim(out[2]) == 0
    assert len(records) == 2
    for r in records:
        assert 0 < r["rows_present"] <= r["rows_buffered"]
        assert r["loss"] == pytest.approx(
            float(np.log(TOY["vocab_size"])), rel=0.02)


@pytest.mark.parametrize("seed", [0, 2147483777])
def test_a_run_starts_with_the_held_experts_level_on_its_batch(seed):
    """``make_state`` of the benchmark's family moves the held experts'
    entries of each expert layer's selection bias until each held expert
    receives its uniform share of the cell's one batch, made again from the
    seed as ``run.py`` makes it; every other parameter is the program's own
    from the key."""
    from perfbench import traffic as traffic_mod

    built = FAMILY.build(TOY, TRAFFIC, None)
    key = jax.random.PRNGKey(seed % 2**32)
    params, opt_state = jax.jit(built.make_state)(key)
    no_sweep = dict(TOY["train"], selection_bias=dict(
        TOY["train"]["selection_bias"], sweeps=0))
    plain = jax.jit(FAMILY.build(
        dict(TOY, train=no_sweep), TRAFFIC, None).make_state)(key)[0]
    held, index = TOY["num_experts"], TOY["expert_shard"]["index"]
    mine = slice(index * held, (index + 1) * held)
    moved = 0
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(params),
                            jax.tree.leaves(plain)):
        a, b = np.asarray(a), np.asarray(b)
        if path[-1].key == "router_bias":
            assert not b.any() and a[mine].any()
            a = a.copy()
            a[mine] = 0
            moved += 1
        np.testing.assert_array_equal(a, b)
    assert moved == 4
    tokens = traffic_mod.resident_tokens(seed, TRAFFIC, TOY["vocab_size"])
    batch = {"input_ids": jnp.asarray(tokens[:, :-1]),
             "labels": jnp.asarray(tokens[:, 1:])}
    share = 4 * 64 * TOY["num_experts_per_tok"] / TOY["num_experts_published"]
    load = np.asarray(built.loss_with_parts(params, batch)[1][
        "tokens_per_expert"])
    unlevelled = np.asarray(built.loss_with_parts(plain, batch)[1][
        "tokens_per_expert"])
    assert load.shape == unlevelled.shape == (4, held)
    assert np.abs(load - share).max() <= 0.08 * share
    assert np.abs(unlevelled - share).max() > 0.15 * share


# ----------------------------------------------------------------------
# the configuration file
# ----------------------------------------------------------------------

def test_the_configuration_holds_the_published_widths():
    published = {
        "hidden_size": 2048, "intermediate_size": 7168,
        "moe_intermediate_size": 1792, "num_attention_heads": 32,
        "num_key_value_heads": 8, "conv_L_cache": 3, "conv_bias": False,
        "num_experts_published": 32, "num_experts_per_tok": 4,
        "norm_topk_prob": True, "routed_scaling_factor": 1,
        "use_expert_bias": True, "norm_eps": 1e-5, "rope_theta": 1000000,
        "max_position_embeddings": 128000, "model_type": "lfm2_moe"}
    assert {k: CELL[k] for k in published} == published
    assert CELL["published"] == {"num_hidden_layers": 24,
                                 "num_dense_layers": 2, "num_experts": 32,
                                 "vocab_size": 65536}
    cut = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8,
           "vocab_size": 65536 // 4}
    assert {k: CELL[k] for k in cut} == cut
    assert set(CELL["reduced_note"]) == set(cut)
    bench = _json("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "lfm2-8b-a1b")
    assert set(entry["reduced"]) == set(cut)
    assert entry["source"] == CELL["source"]
    assert CELL["expert_shard"] == {"index": 0, "of": 4}
    assert "4 v5e chips" in CELL["deployment"]
    # the published list, whole; the layers run are layer 0 and one whole
    # period after the two dense layers
    assert tuple(CELL["layer_types"]) == lfm2.PUBLISHED_LAYER_TYPES
    assert CELL["kept_layers"] == [0, 2, 3, 4, 5]
    assert FAMILY.layers_run(CELL) == (
        (0, "conv", True), (2, "full_attention", False), (3, "conv", False),
        (4, "conv", False), (5, "conv", False))
    assert {"tie_embedding", "initializer_range", "taps", "block", "conv",
            "attention", "routed", "route_eps", "router_bias"} <= set(
        CELL["assumed"])
    assert CELL["route_eps"] == 1e-6
    assert CELL["train"]["selection_bias"]["update_rate"] == 0.001


def test_the_familys_count_is_the_state_the_program_makes():
    built = FAMILY.build(CELL, {"remat": True, "batch": 4, "seq": 8192},
                         None)
    params, _ = jax.eval_shape(built.make_state, jax.random.PRNGKey(0))
    made = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert FAMILY.num_params(CELL) == made == 507_820_288
    layer = params["layers_2"]
    assert layer["attn"]["q_proj"]["kernel"].shape == (2048, 32 * 64)
    assert layer["attn"]["k_proj"]["kernel"].shape == (2048, 8 * 64)
    assert layer["attn"]["q_norm"]["scale"].shape == (64,)
    assert layer["moe"]["router"].shape == (2048, 32)
    assert layer["moe"]["experts_wi"].shape == (8, 2048, 2 * 1792)
    assert "shared_experts" not in layer["moe"]
    assert params["layers_3"]["conv"]["in_proj"]["kernel"].shape == (
        2048, 3 * 2048)
    assert params["layers_3"]["conv"]["conv_weight"].shape == (3, 2048)
    assert params["layers_0"]["mlp"]["up_proj"]["kernel"].shape == (2048,
                                                                    7168)
    assert params["embed"]["embedding"].shape == (16384, 2048)
    assert "lm_head" not in params

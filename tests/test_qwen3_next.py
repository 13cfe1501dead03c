"""The gated-delta-rule, gated-attention, softmax-routed-expert model
(``ray_tpu.models.qwen3_next``), held to the plain reference
``perfbench/families/qwen3_next_reference.py`` at small sizes on the CPU,
seeded weights, no cluster; softmax routing and the gated shared expert;
the shares' sum; partial rotary positions; the zero-centred norm; the plain
causal convolution; its configuration file held to the published widths; the
family's count held to the state the program makes."""

import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import worker
from ray_tpu._private import steptrace
from ray_tpu.models import mla_moe, qwen3_next
from ray_tpu.ops import conv, delta, moe
from tests.conftest import kernel_calls

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


TOY = _json("perfbench", "tests", "configs", "tiny-qwen3-next.json")
CELL = _json("perfbench", "configs", "qwen3-next-80b-a3b.json")
REFERENCE = worker.load_reference(ROOT, TOY)
FAMILY = worker.load_family(ROOT, TOY)


def _tokens(seed, vocab=TOY["vocab_size"], batch=4, seq=64):
    return np.random.default_rng(seed).integers(
        0, vocab, (batch, seq + 1), dtype=np.int32)


def _small(**kw):
    return _small_cached(tuple(sorted(kw.items())))


@functools.lru_cache(maxsize=None)
def _small_cached(items):
    config = qwen3_next.Qwen3NextConfig.small_test(dtype=jnp.float32,
                                                   **dict(items))
    model = qwen3_next.Qwen3Next(config)
    tokens = _tokens(1, vocab=config.vocab_size, batch=2)
    batch = {"input_ids": jnp.asarray(tokens[:, :-1]),
             "labels": jnp.asarray(tokens[:, 1:])}

    @jax.jit
    def init(key):
        # weights large enough that every layer moves the output; norms'
        # scales and the decays off their initial values
        params = model.init(key, batch["input_ids"])["params"]
        keys = iter(jax.random.split(jax.random.PRNGKey(5), 200))
        return jax.tree.map(
            lambda p: p * 3.0 if p.ndim >= 2
            else p + 0.3 * jax.random.normal(next(keys), p.shape), params)

    return config, model, init(jax.random.PRNGKey(0)), batch


def _as_reference(config):
    index, of = config.expert_shard
    names = ("num_attention_heads", "num_key_value_heads", "head_dim",
             "partial_rotary_factor", "rope_theta", "linear_num_key_heads",
             "linear_num_value_heads", "linear_key_head_dim",
             "linear_value_head_dim", "num_experts_per_tok", "norm_topk_prob",
             "rms_norm_eps")
    return {**{name: getattr(config, name) for name in names},
            "layer_types": list(config.layer_types),
            "kept_layers": list(config.kept_layers),
            "expert_shard": {"index": index, "of": of}}


_WIDE = dict(expert_shard=(1, 2), kept_layers=(2, 3),
             linear_num_key_heads=1, linear_num_value_heads=2,
             linear_key_head_dim=128, linear_value_head_dim=128)


@functools.lru_cache(maxsize=None)
def _reference_answers():
    """(hidden states, loss, gradients) of the plain reference on
    ``_small(**_WIDE)``: both cases below hold the model to them."""
    config, _, params, batch = _small(**_WIDE)
    m = _as_reference(config)
    ids, labels = batch["input_ids"], batch["labels"]

    @jax.jit
    def theirs(params):
        loss, grads = jax.value_and_grad(REFERENCE.loss)(
            params, ids, labels, m=m)
        return REFERENCE.hidden_states(params, ids, m=m), loss, grads

    with jax.default_matmul_precision("highest"):
        return theirs(params)


@pytest.mark.parametrize("kernels", [False, True], ids=["scan", "interpret"])
def test_the_model_is_the_reference(kernels, monkeypatch):
    """Hidden states, loss and every gradient of the model against the
    plain reference over one layer of each kind (published 2 and 3), with
    the rule by its chunked scan and by its kernels in interpret mode (head
    widths of 128, so that the kernels take them); a share of the experts
    (1 of 2)."""
    if kernels:
        monkeypatch.setattr(
            delta, "auto_impl", lambda q, v: "pallas_interpret")
        jax.clear_caches()
    config, model, params, batch = _small(**_WIDE)

    @jax.jit
    def ours(params):
        (loss, aux), grads = jax.value_and_grad(
            lambda p: qwen3_next.loss_fn(p, model, batch),
            has_aux=True)(params)
        return (model.apply({"params": params}, batch["input_ids"])[0], loss,
                aux, grads)

    with jax.default_matmul_precision("highest"):
        hidden, loss, aux, grads = ours(params)
    want, ref_loss, ref_grads = _reference_answers()
    np.testing.assert_allclose(hidden, want, rtol=2e-4, atol=2e-4)
    assert aux["tokens_per_expert"].shape == (2, 4)
    np.testing.assert_allclose(loss, ref_loss, rtol=1e-5)
    for (path, g), r in zip(jax.tree_util.tree_leaves_with_path(grads),
                            jax.tree.leaves(ref_grads)):
        # A_log's and dt_bias's gradients are sums over every position that
        # nearly cancel: held to the size of what is summed
        few = g.size <= 4
        np.testing.assert_allclose(
            g, r, rtol=2e-2 if few else 2e-3,
            atol=3e-4 * float(jnp.abs(r).max()) + 1e-9,
            err_msg=jax.tree_util.keystr(path))
    # the selection bias takes no gradient; the head is untied
    assert not np.asarray(grads["layers_2"]["moe"]["router_bias"]).any()
    assert params["lm_head"].shape == params["embed"]["embedding"].shape
    if kernels:
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda p: qwen3_next.loss_fn(p, model, batch)[0]))(params)
        assert kernel_calls(jaxpr) == {"gated_delta_fwd": 1,
                                       "gated_delta_bwd": 1}
    jax.clear_caches()


def test_the_layers_run_are_the_kept_ones_and_leave_their_record():
    """Parameters are named by the published index; three layers of four
    are ``linear_attention``; a traced model writes ``model/layer_kinds``
    and each rule call ``delta/rule``."""
    config, model, params, batch = _small(kept_layers=(2, 3, 4))
    assert config.layers == ((2, "linear_attention"), (3, "full_attention"),
                             (4, "linear_attention"))
    assert {k for k in params if k.startswith("layers_")} == {
        "layers_2", "layers_3", "layers_4"}
    assert set(params["layers_2"]) == {"input_norm", "linear_attn",
                                       "post_attn_norm", "moe"}
    assert set(params["layers_3"]["attn"]) == {
        "q_proj", "k_proj", "v_proj", "o_proj", "q_norm", "k_norm"}
    assert set(params["layers_2"]["linear_attn"]) == {
        "in_proj_qkvz", "in_proj_ba", "conv_weight", "A_log", "dt_bias",
        "norm", "out_proj"}
    assert set(params["layers_2"]["moe"]) == {
        "router", "router_bias", "experts_wi", "experts_wo",
        "shared_experts", "shared_gate"}
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        jax.clear_caches()
        loss = jax.jit(lambda p: qwen3_next.loss_fn(p, model, batch)[0])(
            params)
        records = [r for r in steptrace.snapshot() if r["kind"] == "counters"]
    finally:
        steptrace.set_enabled(False)
        jax.clear_caches()
    kinds = [r["values"] for r in records if r["name"] == "model/layer_kinds"]
    assert kinds[-1] == {"linear_attention": 2, "full_attention": 1,
                         "expert": 3, "layers": 3, "published_layers": 8}
    rules = [r["values"] for r in records if r["name"] == "delta/rule"]
    assert rules and all((r["heads"], r["key_heads"], r["d_k"], r["d_v"],
                          r["tokens"], r["sequences"]) == (4, 2, 16, 16, 128,
                                                           2) for r in rules)
    assert np.isfinite(float(loss))


# ----------------------------------------------------------------------
# the parts
# ----------------------------------------------------------------------

def test_softmax_routing_against_a_hand_computation():
    """``score="softmax"``: p = softmax over ALL experts, the k largest,
    weights p / their sum; no bias (None) is a zero bias; the default is
    the sigmoid the three older families route by."""
    x = jax.random.normal(jax.random.PRNGKey(0), (64, 32))
    router = jax.random.normal(jax.random.PRNGKey(1), (32, 8))
    experts, weights = moe.topk_routing(x, router, None, 3, eps=0.0,
                                        score="softmax")
    logits = np.asarray(jnp.dot(x, router, precision="highest"), np.float64)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    order = np.argsort(-p, axis=-1)[:, :3]
    np.testing.assert_array_equal(np.sort(experts, -1), np.sort(order, -1))
    chosen = np.take_along_axis(p, np.asarray(experts), axis=-1)
    np.testing.assert_allclose(weights, chosen / chosen.sum(-1, keepdims=True),
                               rtol=1e-5)
    same, same_w = moe.topk_routing(x, router, jnp.zeros((8,)), 3, eps=0.0,
                                    score="softmax")
    np.testing.assert_array_equal(experts, same)
    np.testing.assert_array_equal(weights, same_w)
    _, unnormalised = moe.topk_routing(x, router, None, 3, normalize=False,
                                       score="softmax")
    np.testing.assert_allclose(unnormalised, chosen, rtol=1e-5)
    _, sigmoid = moe.topk_routing(x, router, jnp.zeros((8,)), 3)
    _, named = moe.topk_routing(x, router, jnp.zeros((8,)), 3,
                                score="sigmoid")
    np.testing.assert_array_equal(sigmoid, named)
    assert float(jnp.abs(sigmoid - weights).max()) > 1e-3


def _expert_layer(index, of, gate=True):
    return mla_moe.RoutedExperts(
        experts=8, expert_shard=(index, of), width=16, per_token=3,
        scale=1.0, normalize=True, shared=1, dtype=jnp.float32,
        kernel_init=jax.nn.initializers.normal(0.5), eps=0.0,
        score="softmax", shared_gate=gate)


_M = {"num_experts_per_tok": 3, "norm_topk_prob": True}


def test_the_shared_experts_gate_is_a_field():
    """Off, the layer has no such parameter and is the three older
    families' function; on, the shared expert is multiplied by
    ``sigmoid(x w_g)``."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32))
    gated = _expert_layer(0, 2).init(jax.random.PRNGKey(1), x)["params"]
    plain = {k: v for k, v in gated.items() if k != "shared_gate"}
    assert gated["shared_gate"].shape == (32, 1)
    assert set(_expert_layer(0, 2, gate=False).init(
        jax.random.PRNGKey(1), x)["params"]) == set(plain)
    with jax.default_matmul_precision("highest"):
        y1, n1 = _expert_layer(0, 2).apply({"params": gated}, x)
        y0, n0 = _expert_layer(0, 2, gate=False).apply({"params": plain}, x)
        shared = REFERENCE._swiglu(x, gated["shared_experts"])
        np.testing.assert_array_equal(n0, n1)
        np.testing.assert_allclose(
            y0 - y1, (1 - jax.nn.sigmoid(x @ gated["shared_gate"])) * shared,
            rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("of", [2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(of):
    """The routed parts of all ``of`` shares, and the gated shared expert
    that every chip computes alike counted once, are the uncut reference's
    layer; each share is the reference's share; every pair fell on exactly
    one share."""
    keys = jax.random.split(jax.random.PRNGKey(4), 2)
    x = jax.random.normal(keys[0], (2, 48, 32))
    whole = _expert_layer(0, 1).init(keys[1], x)["params"]

    def share(index):
        held = 8 // of
        rows = slice(index * held, (index + 1) * held)
        return {**whole, "experts_wi": whole["experts_wi"][rows],
                "experts_wo": whole["experts_wo"][rows]}

    with jax.default_matmul_precision("highest"):
        parts = [_expert_layer(i, of).apply({"params": share(i)}, x)
                 for i in range(of)]
        shared = (jax.nn.sigmoid(x @ whole["shared_gate"])
                  * REFERENCE._swiglu(x, whole["shared_experts"]))
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


def test_partial_rotary_leaves_the_rest_untouched():
    """The first ``rotary`` dimensions are turned (dimension i against i +
    rotary / 2, position 0 not at all), the others pass as they are; the
    reference's own rotation agrees."""
    from ray_tpu.models.llama import rope_frequencies

    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 4, 32))
    positions = jnp.broadcast_to(jnp.arange(16)[None, :], (2, 16))
    cos, sin = rope_frequencies(8, positions, 1e7)
    y = qwen3_next.rotate_part(x, cos, sin)
    np.testing.assert_array_equal(y[..., 8:], x[..., 8:])
    np.testing.assert_allclose(y[:, 0], x[:, 0], atol=1e-6)
    assert float(jnp.abs(y[:, 1:, :, :8] - x[:, 1:, :, :8]).max()) > 0.1
    np.testing.assert_allclose(y, REFERENCE._rotate_part(x, 1e7, 8),
                               rtol=1e-5, atol=1e-5)
    c = qwen3_next.Qwen3NextConfig()
    assert (c.rotary_dim, c.head_dim) == (64, 256)


def test_the_norms():
    """Zero-centred: ``x / rms(x) * (1 + w)``, the identity's scale at its
    initial ``w`` = 0. The gated norm: ``x / rms(x) * w * silu(z)``, ``w``
    initialised 1."""
    x = 3.0 * jax.random.normal(jax.random.PRNGKey(0), (4, 32))
    z = jax.random.normal(jax.random.PRNGKey(1), (4, 32))
    norm = qwen3_next.ZeroCentredRMSNorm(1e-6, jnp.float32)
    params = norm.init(jax.random.PRNGKey(2), x)["params"]
    assert not np.asarray(params["scale"]).any()
    unit = x / jnp.sqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(norm.apply({"params": params}, x), unit,
                               rtol=1e-6)
    w = jnp.linspace(-0.5, 0.5, 32)
    np.testing.assert_allclose(norm.apply({"params": {"scale": w}}, x),
                               unit * (1 + w), rtol=1e-6)
    gated = qwen3_next.GatedRMSNorm(1e-6, jnp.float32)
    params = gated.init(jax.random.PRNGKey(2), x, z)["params"]
    np.testing.assert_array_equal(params["scale"], 1.0)
    np.testing.assert_allclose(
        gated.apply({"params": {"scale": w}}, x, z),
        unit * w * jax.nn.silu(z), rtol=1e-6)


def test_the_causal_convolution_against_a_loop():
    """Four taps, a SiLU: each position's sum over its own and the three
    positions before it, zeros before the sequence's start; value and both
    gradients against a position-by-position loop; bfloat16 in, bfloat16
    out, the sum in float32."""
    keys = jax.random.split(jax.random.PRNGKey(0), 3)
    x = jax.random.normal(keys[0], (2, 12, 8))
    taps = jax.random.normal(keys[1], (4, 8))
    w = jax.random.normal(keys[2], (2, 12, 8))

    def loop(x, taps):
        rows = []
        for t in range(x.shape[1]):
            acc = jnp.zeros_like(x[:, 0])
            for k in range(4):
                if t - 3 + k >= 0:
                    acc = acc + taps[k] * x[:, t - 3 + k]
            rows.append(jax.nn.silu(acc))
        return jnp.stack(rows, axis=1)

    got = jax.vjp(lambda x, t: conv.causal_conv(x, t, jax.nn.silu), x, taps)
    want = jax.vjp(loop, x, taps)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-6)
    for a, b in zip(got[1](w), want[1](w)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        conv.causal_conv(x, taps), jax.vjp(
            lambda x, t: conv.causal_conv(x, t, lambda s: s), x, taps)[0])
    low = conv.causal_conv(x.astype(jnp.bfloat16), taps, jax.nn.silu)
    assert low.dtype == jnp.bfloat16
    np.testing.assert_allclose(low.astype(jnp.float32), want[0], atol=0.05)


# ----------------------------------------------------------------------
# the benchmark's family and the configuration file
# ----------------------------------------------------------------------

def test_the_configuration_holds_the_published_widths():
    row = None
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(r for r in map(json.loads, f)
                       if r["name"] == "Qwen3-Next-80B-A3B-Instruct")
    cut = {"num_hidden_layers": 4, "num_experts": 32,
           "vocab_size": 151936 // 8}
    published = {
        "hidden_size": 2048, "head_dim": 256, "num_attention_heads": 16,
        "num_key_value_heads": 2, "partial_rotary_factor": 0.25,
        "rope_theta": 10000000, "full_attention_interval": 4,
        "linear_num_key_heads": 16, "linear_num_value_heads": 32,
        "linear_key_head_dim": 128, "linear_value_head_dim": 128,
        "linear_conv_kernel_dim": 4, "moe_intermediate_size": 512,
        "shared_expert_intermediate_size": 512, "num_experts_per_tok": 10,
        "norm_topk_prob": True, "rms_norm_eps": 1e-6,
        "num_experts_published": 512, "tie_word_embeddings": False,
        "model_type": "qwen3_next", "intermediate_size": 5120,
        "mlp_only_layers": [], "decoder_sparse_step": 1}
    assert {k: CELL[k] for k in published} == published
    if row is not None:      # every key of the catalog's row, but the cut
        assert CELL["source"] == row["source_url"]
        for key, value in row["config"].items():
            assert CELL[key] == (cut[key] if key in cut else value), key
    assert CELL["published"] == {"num_hidden_layers": 48, "num_experts": 512,
                                 "vocab_size": 151936}
    assert {k: CELL[k] for k in cut} == cut
    assert set(CELL["reduced_note"]) == set(cut)
    bench = _json("BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "qwen3-next-80b-a3b")
    assert entry["reduced"] == list(cut) and entry["source"] == CELL["source"]
    assert CELL["expert_shard"] == {"index": 0, "of": 16}
    assert "16 v5e chips" in CELL["deployment"]
    assert len(CELL["layer_types"]) == 48 and CELL["kept_layers"] == [0, 1, 2,
                                                                      3]
    assert FAMILY.layers_run(CELL) == (
        (0, "linear_attention"), (1, "linear_attention"),
        (2, "linear_attention"), (3, "full_attention"))
    assert {"origin", "initializer_range", "norms", "block",
            "linear_attention", "linear_attention_init", "full_attention",
            "feed_forward", "router_bias", "auxiliary_balance_loss",
            "multi_token_prediction", "final"} <= set(CELL["assumed"])


def test_the_familys_count_is_the_state_the_program_makes():
    built = FAMILY.build(CELL, {"remat": True, "batch": 2, "seq": 8192},
                         None)
    params, _ = jax.eval_shape(built.make_state, jax.random.PRNGKey(0))
    made = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    # ISSUE 56's count, and the selection bias's 512 a layer
    assert FAMILY.num_params(CELL) == made == 625_667_136 + 4 * 512
    linear, full = params["layers_0"]["linear_attn"], params["layers_3"][
        "attn"]
    assert linear["in_proj_qkvz"]["kernel"].shape == (2048, 12288)
    assert linear["in_proj_ba"]["kernel"].shape == (2048, 64)
    assert linear["conv_weight"].shape == (4, 8192)
    assert linear["A_log"].shape == linear["dt_bias"].shape == (32,)
    assert linear["norm"]["scale"].shape == (128,)
    assert linear["out_proj"]["kernel"].shape == (4096, 2048)
    assert full["q_proj"]["kernel"].shape == (2048, 16 * 512)
    assert full["k_proj"]["kernel"].shape == (2048, 2 * 256)
    assert full["q_norm"]["scale"].shape == (256,)
    moe_ = params["layers_3"]["moe"]
    assert moe_["router"].shape == (2048, 512)
    assert moe_["experts_wi"].shape == (32, 2048, 2 * 512)
    assert moe_["shared_gate"].shape == (2048, 1)
    assert params["embed"]["embedding"].shape == params["lm_head"].shape \
        == (18992, 2048)

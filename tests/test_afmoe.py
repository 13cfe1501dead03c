"""The window-and-full-attention, grouped-heads, routed-expert model
(``ray_tpu.models.afmoe``), held to the plain reference
``perfbench/families/afmoe_reference.py`` at small sizes on the CPU, seeded
weights, no cluster; its configuration file held to the published widths;
the benchmark family's step as the worker calls it."""

import dataclasses
import functools
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import compare, worker
from ray_tpu._private import steptrace
from ray_tpu.models import afmoe, mla_moe
from ray_tpu.ops import moe
from tests.conftest import kernel_calls

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


TOY = _json("perfbench", "tests", "configs", "tiny-afmoe.json")
CELL = _json("perfbench", "configs", "trinity-mini.json")
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
    d_loss, d_norm, cos = _differences("float32")
    assert d_loss <= 1e-5 and d_norm <= 1e-4 and cos >= 0.99999, (
        d_loss, d_norm, cos)


# bfloat16 against float32 at the toy size, seeds 0-7 read on the CPU: loss
# 8.5e-7 to 1.1e-4, gradient norm 1.1e-5 to 5.2e-3, cosine 0.99697 to
# 0.99926 (a toy of 64 wide with top-3 of 8 experts moves more pairs than the
# real widths do). The control (weights kept to 3 bits of mantissa, seeds 3,
# 5, 7): loss 2.5e-5 to 4.0e-4, norm 1.0e-2 to 2.1e-2, cosine 0.9706 to
# 0.9735: it is the cosine that tells them apart in every seed, so its limit
# lies between the two readings (1 - cosine: 3.0e-3 against 2.6e-2, limit
# 1e-2); loss and norm at 5x the worst sound reading.
BF16_LIMITS = {"loss": 6e-4, "norm": 2.5e-2, "cosine": 0.99}


@pytest.mark.parametrize("seed", [3, 7])
def test_bfloat16_step_is_inside_the_toy_limits(seed):
    d_loss, d_norm, cos = _differences("bfloat16", seed)
    assert d_loss <= BF16_LIMITS["loss"], d_loss
    assert d_norm <= BF16_LIMITS["norm"], d_norm
    assert cos >= BF16_LIMITS["cosine"], cos


def test_a_step_in_a_lower_precision_is_outside_them():
    d_loss, d_norm, cos = _differences("bfloat16", round_weights=True)
    assert (d_loss > BF16_LIMITS["loss"] or d_norm > BF16_LIMITS["norm"]
            or cos < BF16_LIMITS["cosine"]), (d_loss, d_norm, cos)


# ----------------------------------------------------------------------
# the model against the reference: logits, loss, gradient
# ----------------------------------------------------------------------

def _small(**kw):
    config = afmoe.AfmoeConfig.small_test(dtype=jnp.float32, **kw)
    model, params = afmoe.init_params(config, jax.random.PRNGKey(1))
    # norms' scales away from one, so that a misplaced norm shows
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 64))
    params = jax.tree.map(
        lambda x: x + 0.1 * jax.random.normal(next(keys), x.shape)
        if x.ndim == 1 and x.shape[0] != config.num_experts else x, params)
    tokens = _tokens(5, config.vocab_size, 2, 32)
    batch = {"input_ids": jnp.asarray(tokens[:, :-1]),
             "labels": jnp.asarray(tokens[:, 1:])}
    return config, model, params, batch


def _as_reference(config):
    """The configuration file's keys for the program's ``config``."""
    index, of = config.expert_shard
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "intermediate_size", "moe_intermediate_size", "num_dense_layers",
            "num_shared_experts", "num_experts_per_tok", "route_scale",
            "route_norm", "sliding_window", "rope_theta", "rms_norm_eps")
    return {**{k: getattr(config, k) for k in keys},
            "layer_types": list(config.layer_types),
            "expert_shard": {"index": index, "of": of}}


@pytest.mark.parametrize("attention", ["xla", "scan", "kernel_results",
                                       "kernel_rotary"])
def test_the_model_is_the_reference(attention, monkeypatch, request):
    """Logits, loss and every parameter's gradient, float32 on both sides:
    five layers (a dense one, a full one among the expert layers), two
    key-value heads for four query heads, a window of 8 in 32 positions,
    half the experts held. ``scan`` is the path that stands for the kernel
    where there is no chip; ``kernel_results`` is the kernel itself in
    interpret mode at the published head width of 128 with four blocks of
    keys a head, the boundary the cell's calls take since PR 55: its
    output, dQ, dK and dV written into the model's [B, T, H x 128] arrays
    and the gate's cotangent read from one, with the heads' norm and the
    rotation XLA's; ``kernel_rotary`` is that boundary with the prologue's
    kernel pair under the one ``custom_vjp``
    (``ops.attention.normed_rotary_self_attention``, interpreted), rotated
    in the window layers and normed alone in the full one."""
    from ray_tpu.ops import attention as ops_attention, flash_kernels

    more = {}
    if attention == "scan":
        monkeypatch.setattr(
            ops_attention, "causal_self_attention",
            lambda q, k, v, path, window: ops_attention.flash_attention(
                *(t.transpose(0, 2, 1, 3) for t in (q, k, v)), causal=True,
                window=window, impl="scan", block_k=8).transpose(0, 2, 1, 3))
    else:
        more = {"head_dim": 128}
        monkeypatch.setattr(flash_kernels, "_MAX_RESIDENT", 8)
        monkeypatch.setattr(
            ops_attention, "flash_attention", functools.partial(
                ops_attention.flash_attention, impl="pallas_interpret",
                block_q=8, block_k=8))
        assert ops_attention.results_in_model_arrays(32, 128, 128)
        if attention == "kernel_results":
            real = ops_attention.causal_self_attention
            monkeypatch.setattr(
                ops_attention, "causal_self_attention",
                lambda q, k, v, path, window: real(q, k, v, "flash", window))
        else:
            # the layers' calls ("flash"; the initialiser's are "xla")
            monkeypatch.setattr(
                afmoe, "normed_rotary_self_attention",
                lambda *a, attention, **kw:
                ops_attention.normed_rotary_self_attention(
                    *a, attention=attention, **kw, **(dict(
                        impl="pallas_interpret", block_q=8, block_k=8)
                        if attention == "flash" else {})))
            more["attention"] = "flash"
        jax.clear_caches()  # flash_attention is jitted: the rule is read
        request.addfinalizer(jax.clear_caches)
    config, model, params, batch = _small(expert_shard=(1, 2), **more)
    m = _as_reference(config)
    with jax.default_matmul_precision("highest"):
        hidden, _ = model.apply({"params": params}, batch["input_ids"])
        got_logits = hidden @ params["lm_head"].T
        want_logits = REFERENCE.logits(params, batch["input_ids"], m=m)
        (loss, aux), grads = jax.value_and_grad(afmoe.loss_fn, has_aux=True)(
            params, model, batch)
        want_loss, want_grads = jax.value_and_grad(REFERENCE.loss)(
            params, batch["input_ids"], batch["labels"], m=m, remat=True)
    np.testing.assert_allclose(got_logits, want_logits, atol=2e-4, rtol=2e-4)
    assert float(loss) == pytest.approx(float(want_loss), rel=1e-5)
    assert aux["tokens_per_expert"].shape == (4, 4)
    flat, want_flat = (dict(jax.tree_util.tree_leaves_with_path(t))
                       for t in (grads, want_grads))
    assert flat.keys() == want_flat.keys()
    for path, got in flat.items():
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:   # moves the selection, takes no gradient
            assert not np.asarray(got).any(), name
            continue
        scale = float(jnp.abs(want_flat[path]).max())
        assert scale > 0, name
        np.testing.assert_allclose(got, want_flat[path], atol=2e-4 * scale,
                                   rtol=2e-3, err_msg=name)


def test_positions_are_on_the_window_layers_only():
    """Rotary positions go with the window: swapping the kinds of two layers
    changes the loss, in the program and in the reference alike, and each
    still agrees with the other; a model of full layers alone, which carries
    no positional term at all, gives its last token the same logits whatever
    the order of the tokens before it."""
    config, model, params, batch = _small()
    assert config.layer_types == (afmoe.WINDOW,) * 3 + (afmoe.FULL,
                                                        afmoe.WINDOW)
    swapped = dataclasses.replace(
        config, layer_types=(afmoe.WINDOW, afmoe.WINDOW, afmoe.FULL,
                             afmoe.WINDOW, afmoe.WINDOW))
    losses = {}
    with jax.default_matmul_precision("highest"):
        for name, c in (("published", config), ("swapped", swapped)):
            losses[name] = (
                float(afmoe.loss_fn(params, afmoe.Afmoe(c), batch)[0]),
                float(REFERENCE.loss(params, batch["input_ids"],
                                     batch["labels"], m=_as_reference(c))))
    for ours, theirs in losses.values():
        assert ours == pytest.approx(theirs, rel=1e-5)
    assert abs(losses["published"][0] - losses["swapped"][0]) > 1e-4
    # one layer, so that the earlier tokens' own states do not depend on
    # their order: a full layer does not see a permutation of them, a
    # window layer (the window holds all 31) does, by its positions alone
    ids = batch["input_ids"][:1]
    shuffled = jnp.concatenate([ids[:, :-1][:, ::-1], ids[:, -1:]], axis=1)

    def last(kind, x):
        c = dataclasses.replace(config, num_hidden_layers=1,
                                layer_types=(kind,), sliding_window=64)
        return afmoe.Afmoe(c).apply({"params": params}, x)[0][0, -1]

    with jax.default_matmul_precision("highest"):
        np.testing.assert_allclose(last(afmoe.FULL, ids),
                                   last(afmoe.FULL, shuffled),
                                   atol=2e-5, rtol=2e-4)
        assert float(jnp.abs(last(afmoe.WINDOW, ids)
                             - last(afmoe.WINDOW, shuffled)).max()) > 1e-3


def test_recomputation_changes_no_value_and_keeps_the_kernels_output(
        monkeypatch):
    """With ``remat`` the gradient is the same to the bit; on a TPU (where
    ``auto`` is the kernel at head width 128) a step's jaxpr holds one
    forward and one backward call a layer, windowed in the window layers,
    and no forward call again: ``ops.remat.remat_policy``. Where a head is
    several blocks of keys (the ``model_results`` boundary, the cell's) the
    heads' norm and the rotation are ``ops/rotary.py``'s kernels, of which
    nothing is kept: ``head_rotary_fwd`` for q and for k a layer, run and
    recomputed, ``head_rotary_bwd`` once for each, the flash calls as
    before."""
    from ray_tpu.ops import flash_kernels

    config, model, params, batch = _small()
    grad = lambda c: jax.jit(jax.grad(lambda p: afmoe.loss_fn(
        p, afmoe.Afmoe(c), batch)[0]))
    plain = grad(config)(params)
    kept = grad(dataclasses.replace(config, remat=True))(params)
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(a, b)
    wide = afmoe.AfmoeConfig.small_test(
        head_dim=128, sliding_window=128, remat=True, attention="auto")
    # made off the "TPU": the initialiser runs the model, and the expert
    # layer's unwritten buffers are a TPU kernel's
    _, wide_params = afmoe.init_params(wide, jax.random.PRNGKey(0))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    def step_calls(seq):
        ids = jnp.zeros((1, seq), jnp.int32)
        return kernel_calls(jax.make_jaxpr(jax.grad(lambda p: afmoe.loss_fn(
            p, afmoe.Afmoe(wide), {"input_ids": ids, "labels": ids})[0]))(
                wide_params))

    try:
        calls = step_calls(512)
        monkeypatch.setattr(flash_kernels, "_MAX_RESIDENT", 256)
        jax.clear_caches()
        blocks = step_calls(1024)     # four blocks of keys a head
    finally:
        jax.clear_caches()
    assert (blocks.pop("head_rotary_fwd"), blocks.pop("head_rotary_bwd")) == (
        4 * wide.num_hidden_layers, 2 * wide.num_hidden_layers)
    assert blocks == calls
    # an expert layer's loops start from buffers nobody filled (PR 46): two
    # forward, two in the recomputed forward (a block's last norm reads the
    # layer's result), five backward
    assert calls.pop("unwritten") == (2 + 2 + 5) * (
        wide.num_hidden_layers - wide.num_dense_layers)
    assert calls == {
        "flash_fwd_w128": 4, "flash_bwd_w128": 4, "flash_fwd": 1,
        "flash_bwd": 1}


# ----------------------------------------------------------------------
# the expert layer, as this model calls it
# ----------------------------------------------------------------------

def _expert_layer(index, of):
    return mla_moe.RoutedExperts(
        experts=8, expert_shard=(index, of), width=16, per_token=3,
        scale=2.826, normalize=True, shared=1, dtype=jnp.float32,
        kernel_init=jax.nn.initializers.normal(0.2))


@pytest.mark.parametrize("of", [2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(of):
    """The routed parts of all ``of`` shares, the shared expert counted
    once, are the uncut reference's layer; each share is the reference's
    share; every pair fell on exactly one share."""
    keys = jax.random.split(jax.random.PRNGKey(4), 2)
    x = jax.random.normal(keys[0], (2, 48, 32))
    whole = _expert_layer(0, 1).init(keys[1], x)["params"]
    m = {"num_experts_per_tok": 3, "route_norm": True, "route_scale": 2.826}

    def share(index):
        held = 8 // of
        rows = slice(index * held, (index + 1) * held)
        return {**whole, "experts_wi": whole["experts_wi"][rows],
                "experts_wo": whole["experts_wo"][rows]}

    with jax.default_matmul_precision("highest"):
        once = REFERENCE._swiglu(x, whole["shared_experts"])
        parts = [_expert_layer(i, of).apply({"params": share(i)}, x)
                 for i in range(of)]
        uncut = REFERENCE._experts(
            x, whole, {**m, "expert_shard": {"index": 0, "of": 1}})
        routed = sum(y - once for y, _ in parts)
        np.testing.assert_allclose(routed + once, uncut, rtol=2e-4, atol=2e-5)
        assert sum(int(n.sum()) for _, n in parts) == 2 * 48 * 3
        for i in (0, of - 1):
            np.testing.assert_allclose(
                parts[i][0], REFERENCE._experts(
                    x, share(i),
                    {**m, "expert_shard": {"index": i, "of": of}}),
                rtol=2e-4, atol=2e-5)


def test_the_step_is_the_one_builder_and_reports_the_experts_load():
    """``afmoe.build_train_step`` is ``parallel.build_train_step`` with the
    auxiliary output; the selection bias stays where it was initialised; a
    loop's report carries the loss and the experts' load, and the step
    observatory gets them as one ``train/step_aux`` record."""
    config, model, params, batch = _small(expert_shard=(0, 2))
    tx = afmoe.make_optimizer()
    pairs = batch["input_ids"].size * config.num_experts_per_tok
    step = afmoe.build_train_step(model, tx, donate=False)
    new_params, _, loss, tokens = step(params, tx.init(params), batch)
    assert tokens.shape == (4, 4)
    assert not np.asarray(
        new_params["layers_1"]["moe"]["router_bias"]).any()
    assert np.asarray(new_params["layers_1"]["moe"]["router"] !=
                      params["layers_1"]["moe"]["router"]).any()
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        metrics = afmoe.step_metrics(loss, tokens, pairs=pairs)
        records = [r for r in steptrace.snapshot()
                   if r["kind"] == "counters"]
    finally:
        steptrace.set_enabled(False)
    assert set(metrics) == {"loss", "expert_tokens_max", "expert_tokens_mean",
                            "rows_present", "rows_buffered", "rows_fill"}
    present = np.asarray(tokens).sum(axis=1)
    assert metrics["rows_present"] == int(present.sum())
    rungs = moe.row_buffer_rungs(pairs)
    assert metrics["rows_buffered"] == sum(
        min(n for n in rungs if n >= p) for p in present)
    assert 0 < metrics["rows_fill"] <= 1
    assert metrics["expert_tokens_max"] == int(np.asarray(tokens).max())
    assert len(records) == 1 and records[0]["name"] == "train/step_aux"
    assert records[0]["values"] == metrics
    drawn = [e for e in steptrace.chrome_trace(
        steptrace.merge_records(records)) if e["ph"] == "C"]
    assert [(e["name"], e["args"]) for e in drawn] == [
        ("train/step_aux", metrics)]


def test_the_familys_step_is_the_workers_and_fills_the_ring():
    """The benchmark's family hands the worker a step that returns the loss
    third and last, lowered and compiled as the worker lowers and compiles
    it, with the plan the worker reads; every call leaves one
    ``train/step_aux`` record, as a user's loop leaves it. The learning
    rate climbs from 0 (``train.lr_warmup_steps``): the first step moves
    no weight, the second does. After each step the held experts' entries
    of every expert layer's selection bias have moved by
    ``load_balance_coeff`` against the load that step reported (the
    published balance update); the other experts' entries stay zero."""
    built = FAMILY.build(TOY, TRAFFIC, None)
    params, opt_state = jax.jit(built.make_state)(jax.random.PRNGKey(0))
    tokens = _tokens(0)
    batch = {"input_ids": jnp.asarray(tokens[:, :-1]),
             "labels": jnp.asarray(tokens[:, 1:])}
    compiled = built.step.lower(params, opt_state, batch).compile()
    assert compiled.memory_analysis().temp_size_in_bytes > 0
    assert "ENTRY" in compiled.as_text()
    steptrace.set_enabled(True)
    steptrace.reset()
    held, index = TOY["num_experts"], TOY["expert_shard"]["index"]
    mine = slice(index * held, (index + 1) * held)
    share = 4 * 64 * TOY["num_experts_per_tok"] / TOY["num_experts_published"]

    def biases(tree):
        return np.stack([np.asarray(tree[f"layers_{i}"]["moe"]["router_bias"])
                         for i in range(1, 5)])

    load = np.asarray(built.loss_with_parts(params, batch)[1][
        "tokens_per_expert"])
    before = biases(params)
    try:
        heads = [np.asarray(params["lm_head"])]
        out = compiled(params, opt_state, batch)
        after = biases(out[0])
        heads.append(np.asarray(out[0]["lm_head"]))
        out = built.step(*out[:2], batch)
        heads.append(np.asarray(out[0]["lm_head"]))
        out = built.step(*out[:2], batch)
        records = [r["values"] for r in steptrace.snapshot()
                   if r["kind"] == "counters"
                   and r["name"] == "train/step_aux"]
    finally:
        steptrace.set_enabled(False)
    np.testing.assert_allclose(
        after[:, mine] - before[:, mine],
        TOY["load_balance_coeff"] * np.sign(share - load), atol=1e-7)
    after[:, mine] = 0
    assert not after.any()
    assert len(out) == 3 and np.ndim(out[2]) == 0
    assert len(records) == 3
    pairs = 4 * 64 * TOY["num_experts_per_tok"]
    for r in records:
        assert 0 < r["rows_present"] <= r["rows_buffered"] <= 3 * pairs
    for r in records:
        assert r["loss"] == pytest.approx(
            float(np.log(TOY["vocab_size"])), rel=0.02)
    np.testing.assert_array_equal(heads[1], heads[0])
    assert (heads[2] != heads[1]).any()


@pytest.mark.parametrize("seed", [0, 5, 2147483777])
def test_a_run_starts_with_the_held_experts_level_on_its_batch(seed):
    """``make_state`` of the benchmark's family: the weights are the
    program's own from the key, but for the held experts' entries of each
    expert layer's selection bias, which are moved (``train.selection_bias``)
    until each held expert receives its uniform share of the cell's one
    batch, made again from the seed as ``run.py`` makes it. Under a zero
    bias the same weights spread the same batch unevenly."""
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
    assert moved == TOY["num_hidden_layers"] - TOY["num_dense_layers"]
    assert all(not np.asarray(m).any()
               for m in jax.tree.leaves(opt_state[0].mu))
    tokens = traffic_mod.resident_tokens(seed, TRAFFIC, TOY["vocab_size"])
    batch = {"input_ids": jnp.asarray(tokens[:, :-1]),
             "labels": jnp.asarray(tokens[:, 1:])}
    share = 4 * 64 * TOY["num_experts_per_tok"] / TOY["num_experts_published"]
    load = np.asarray(built.loss_with_parts(params, batch)[1][
        "tokens_per_expert"])
    unlevelled = np.asarray(built.loss_with_parts(plain, batch)[1][
        "tokens_per_expert"])
    assert load.shape == unlevelled.shape == (moved, held)
    assert np.abs(load - share).max() <= 0.05 * share
    assert np.abs(unlevelled - share).max() > 0.2 * share


# ----------------------------------------------------------------------
# the configuration file
# ----------------------------------------------------------------------

def test_the_configuration_holds_the_published_widths():
    published = {
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 4, "head_dim": 128, "intermediate_size": 6144,
        "moe_intermediate_size": 1024, "num_experts_published": 128,
        "num_experts_per_tok": 8, "num_shared_experts": 1,
        "route_scale": 2.826, "route_norm": True, "score_func": "sigmoid",
        "sliding_window": 2048, "rms_norm_eps": 1e-5, "rope_theta": 10000,
        "rope_scaling": None, "global_attn_every_n_layers": 4,
        "tie_word_embeddings": False, "mup_enabled": True,
        "max_position_embeddings": 131072, "n_group": 1, "topk_group": 1}
    assert {k: CELL[k] for k in published} == published
    assert CELL["published"] == {"num_hidden_layers": 32,
                                 "num_dense_layers": 2, "num_experts": 128,
                                 "vocab_size": 200192}
    cut = {"num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 8,
           "vocab_size": 200192 // 8}
    assert {k: CELL[k] for k in cut} == cut
    assert set(CELL["reduced_note"]) == set(cut)
    bench = _json("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "trinity-mini")
    assert set(entry["reduced"]) == set(cut)
    assert entry["source"] == CELL["source"]
    assert CELL["expert_shard"] == {"index": 0, "of": 16}
    assert "16 v5e chips" in CELL["deployment"]
    # the published list, whole, under the published rule; the layers run
    # are its first five: one whole period after the dense layer
    assert len(CELL["layer_types"]) == 32 and all(
        kind == ("full_attention" if (i + 1) % 4 == 0
                 else "sliding_attention")
        for i, kind in enumerate(CELL["layer_types"]))
    run = FAMILY.layer_types(CELL)
    assert run == ("sliding_attention",) * 3 + ("full_attention",
                                                "sliding_attention")
    assert run[CELL["num_dense_layers"]:].count("sliding_attention") == 3
    assert {"embedding", "block", "attention", "routed", "router_bias",
            "auxiliary_balance_loss"} <= set(CELL["assumed"])


def test_the_familys_count_is_the_state_the_program_makes():
    built = FAMILY.build(CELL, {"remat": True, "batch": 1, "seq": 16384},
                         None)
    params, _ = jax.eval_shape(built.make_state, jax.random.PRNGKey(0))
    made = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert FAMILY.num_params(CELL) == made == 504_147_712
    layer = params["layers_1"]
    assert layer["attn"]["k_proj"]["kernel"].shape == (2048, 4 * 128)
    assert layer["attn"]["gate_proj"]["kernel"].shape == (2048, 32 * 128)
    assert layer["attn"]["q_norm"]["scale"].shape == (128,)
    assert layer["moe"]["router"].shape == (2048, 128)
    assert layer["moe"]["experts_wi"].shape == (8, 2048, 2 * 1024)
    assert params["layers_0"]["mlp"]["up_proj"]["kernel"].shape == (2048,
                                                                    6144)
    assert params["embed"]["embedding"].shape == (25024, 2048)
    # half an expert a layer is the routed part's expectation here: 264M
    # parameters of matrices a token
    assert FAMILY.matmul_params_per_token(CELL) == pytest.approx(
        264.0e6, rel=2e-3)
    # the kernels' pairs by each layer's own mask: a window layer's 23.4%
    # of a full layer's at 16,384
    assert FAMILY.attended_pairs_per_token(CELL, 16384) * 16384 == (
        134_225_920 + 4 * 31_458_304)
    flops = FAMILY.train_flops_per_token(CELL, 16384)
    attention = 6 * 32 * 256 * (134_225_920 + 4 * 31_458_304) / 16384
    assert flops == pytest.approx(6 * 264.0e6 + attention, rel=2e-3)
    # 1.28e13 of a step's 3.87e13: a third (by the kernels' own count, which
    # has the backward's scores again, 1.49e13 of 4.09e13)
    assert attention * 16384 == pytest.approx(1.278e13, rel=1e-3)
    assert 0.32 < attention / flops < 0.34

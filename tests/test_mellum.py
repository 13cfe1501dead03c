"""The window-over-full, rotary-in-both, every-layer-routed model
(``ray_tpu.models.mellum``), held to the plain reference
``perfbench/families/mellum_reference.py`` at small sizes on the CPU, seeded
weights, no cluster; the scaled rotary table held to hand-computed values;
its configuration file held to the published widths; the benchmark family's
step as the worker calls it."""

import dataclasses
import functools
import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import compare, worker
from ray_tpu._private import steptrace
from ray_tpu.models import llama, mellum, mla_moe
from tests.conftest import kernel_calls

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


TOY = _json("perfbench", "tests", "configs", "tiny-mellum.json")
CELL = _json("perfbench", "configs", "mellum2-12b-a2.5b.json")
REFERENCE = worker.load_reference(ROOT, TOY)
FAMILY = worker.load_family(ROOT, TOY)
TRAFFIC = {"batch": 4, "seq": 64, "remat": True}
YARN = CELL["rope_parameters"]["full_attention"]


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


# bfloat16 against float32 at the toy size, seeds 0, 1, 2, 3, 5, 7 read on
# the CPU: loss 5.5e-6 to 2.8e-5, gradient norm 3.1e-5 to 1.0e-3, cosine
# 0.99992 to 0.99995. The control (weights kept to 3 bits of mantissa, seeds
# 3, 5, 7): loss 1.9e-5 to 2.0e-4, norm 6.2e-4 to 3.0e-3, cosine 0.9958 to
# 0.9965: it is the cosine that tells them apart in every seed, so its limit
# lies between the two readings (1 - cosine: 8e-5 against 3.5e-3, limit
# 5e-4); loss and norm at 5x the worst sound reading.
BF16_LIMITS = {"loss": 1.5e-4, "norm": 5e-3, "cosine": 0.9995}


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

def _small(seq=32, **kw):
    config = mellum.MellumConfig.small_test(dtype=jnp.float32, **kw)
    model, params = mellum.init_params(config, jax.random.PRNGKey(1))
    # norms' scales away from one, so that a misplaced norm shows
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 64))
    params = jax.tree.map(
        lambda x: x + 0.1 * jax.random.normal(next(keys), x.shape)
        if x.ndim == 1 and x.shape[0] != config.num_experts else x, params)
    tokens = _tokens(5, config.vocab_size, 2, seq)
    batch = {"input_ids": jnp.asarray(tokens[:, :-1]),
             "labels": jnp.asarray(tokens[:, 1:])}
    return config, model, params, batch


def _as_reference(config):
    """The configuration file's keys for the program's ``config``."""
    index, of = config.expert_shard
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "moe_intermediate_size", "num_experts_per_tok", "norm_topk_prob",
            "sliding_window", "rms_norm_eps")
    return {**{k: getattr(config, k) for k in keys},
            "layer_types": list(config.layer_types),
            "rope_parameters": {kind: dict(entry) for kind, entry
                                in config.rope_parameters},
            "expert_shard": {"index": index, "of": of}}


@pytest.mark.parametrize("attention", ["xla", "scan", "kernel_results",
                                       "kernel_rotary"])
def test_the_model_is_the_reference(attention, monkeypatch, request):
    """Logits, loss and every parameter's gradient, float32 on both sides:
    four layers (three window layers and a full one, every one routed), two
    key-value heads for four query heads, a window of 8 in 32 positions,
    half the experts held, a YaRN table on the full layer whose ramp lies
    inside the head's dimensions. ``scan`` is the path that stands for the
    kernel where there is no chip; ``kernel_results`` is the kernel itself
    in interpret mode at the published head width of 128 with four blocks
    of keys a head in the window layers and two in the full one, the
    boundary the cell's calls take, the window half of what a grid step may
    hold and so, by ``flash_kernels._block_sizes``'s rule, a whole block of
    its own (diagonal, trailing and dead blocks told apart), with the heads'
    norm and the rotation XLA's; ``kernel_rotary`` is that boundary with
    the prologue's kernel pair under the one ``custom_vjp``
    (``ops.attention.normed_rotary_self_attention``, interpreted): the flash
    kernels' operands written by ``head_rotary_fwd``, their float32 dQ^T
    and their dK read by ``head_rotary_bwd``, both tables."""
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
        monkeypatch.setattr(flash_kernels, "_MAX_RESIDENT", 16)
        monkeypatch.setattr(flash_kernels, "_WINDOW_RESIDENT_FROM", 8)
        assert flash_kernels.grid_block_kinds(32, 32, True, 8, 8, window=8) \
            == {"whole": 0, "diagonal": 4, "trailing": 3, "dead": 9,
                "looped": 0, "steps": 8, "dead_steps": 1}
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
                mellum, "normed_rotary_self_attention",
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
        (loss, aux), grads = jax.value_and_grad(mellum.loss_fn, has_aux=True)(
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


# ----------------------------------------------------------------------
# the two tables
# ----------------------------------------------------------------------

def test_the_scaled_table_is_the_hand_computed_one():
    """The published entry: the blend starts at dimension 18 and is whole
    from 35 on; three frequencies worked out by hand (below the ramp the
    published one, past it a sixteenth, between them the blend); cos and sin
    carry the attention factor; the table does not move with the length."""
    assert llama.yarn_correction_range(128, 500000.0, 8192, 32, 1) == (18, 35)
    # c(32) = 128 ln(8192 / (64 pi)) / (2 ln 500000) = 18.08; c(1) = 34.98
    turns = lambda r: 128 * math.log(8192 / (2 * math.pi * r)) / (
        2 * math.log(500000))
    assert turns(32) == pytest.approx(18.08, abs=0.01)
    assert turns(1) == pytest.approx(34.98, abs=0.01)
    positions = jnp.arange(8192)[None, :]
    cos, sin = llama.rope_table(128, positions, YARN)
    assert cos.shape == sin.shape == (1, 8192, 64)
    factor = 1.2772588722239782
    assert factor == pytest.approx(0.1 * math.log(16) + 1, rel=1e-12)
    # position 1: the angle is the frequency itself
    inv = np.arctan2(np.asarray(sin[0, 1]), np.asarray(cos[0, 1]))
    plain = lambda i: 500000.0 ** (-2 * i / 128)
    assert inv[5] == pytest.approx(plain(5), rel=1e-5)
    assert inv[5] == pytest.approx(0.358730, rel=1e-5)
    assert inv[40] == pytest.approx(plain(40) / 16, rel=1e-4)
    assert inv[40] == pytest.approx(1.71405e-5, rel=1e-4)
    ramp = (26 - 18) / 17
    assert inv[26] == pytest.approx(
        (1 - ramp) * plain(26) + ramp * plain(26) / 16, rel=1e-4)
    assert inv[26] == pytest.approx(2.70438e-3, rel=1e-4)
    np.testing.assert_allclose(
        np.asarray(cos[0, 1]) ** 2 + np.asarray(sin[0, 1]) ** 2,
        factor ** 2, rtol=1e-5)
    assert float(cos[0, 0, 0]) == pytest.approx(factor, rel=1e-6)
    short = llama.rope_table(128, positions[:, :512], YARN)
    np.testing.assert_array_equal(short[0], cos[:, :512])
    # the reference writes the same table out from the equations
    ref_inv, ref_scale = REFERENCE.inverse_frequencies(128, YARN)
    np.testing.assert_allclose(ref_inv, inv, rtol=2e-4)
    assert ref_scale == factor
    # left out, the factor is 0.1 ln(factor) + 1
    bare = {k: v for k, v in YARN.items() if k != "attention_factor"}
    np.testing.assert_array_equal(
        llama.rope_table(128, positions[:, :64], bare)[0], cos[:, :64])
    with pytest.raises(ValueError):
        llama.rope_table(128, positions, {"rope_type": "linear",
                                          "rope_theta": 1e4})


def test_the_plain_table_is_rope_frequencies_to_the_bit():
    """A ``default`` entry is ``rope_frequencies`` itself, and a YaRN entry
    of factor 1 and attention factor 1 gives its values too; each traced
    call leaves one ``rope/table`` record that says what was built."""
    positions = jnp.arange(300)[None, :]
    want = llama.rope_frequencies(128, positions, 500000.0)
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        got = llama.rope_table(
            128, positions, CELL["rope_parameters"]["sliding_attention"])
        one = llama.rope_table(128, positions, {
            **YARN, "factor": 1, "attention_factor": 1.0})
        llama.rope_table(128, positions, YARN)
        records = [r["values"] for r in steptrace.snapshot()
                   if r["kind"] == "counters" and r["name"] == "rope/table"]
    finally:
        steptrace.set_enabled(False)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    for a, b in zip(one, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-6)
    assert records[0] == {
        "kind": "plain", "theta": 500000.0, "factor": 1.0, "original": 0,
        "low": 0, "high": 0, "attention_factor": 1.0, "dims": 128}
    assert records[2] == {
        "kind": "yarn", "theta": 500000.0, "factor": 16.0, "original": 8192,
        "low": 18, "high": 35, "attention_factor": 1.2772588722239782,
        "dims": 128}


def test_both_kinds_of_layer_carry_positions_from_their_own_table():
    """A full layer is no longer blind to order (``afmoe``'s is): one layer
    of either kind gives its last token other logits when the tokens before
    it change places; swapping the two tables changes the loss, in the
    program and in the reference alike, and each still agrees with the
    other."""
    config, model, params, batch = _small()
    assert config.layer_types == (mellum.WINDOW,) * 3 + (mellum.FULL,)
    ropes = dict(config.rope_parameters)
    swapped = dataclasses.replace(config, rope_parameters={
        mellum.FULL: ropes[mellum.WINDOW], mellum.WINDOW: ropes[mellum.FULL]})
    losses = {}
    with jax.default_matmul_precision("highest"):
        for name, c in (("published", config), ("swapped", swapped)):
            losses[name] = (
                float(mellum.loss_fn(params, mellum.Mellum(c), batch)[0]),
                float(REFERENCE.loss(params, batch["input_ids"],
                                     batch["labels"], m=_as_reference(c))))
    for ours, theirs in losses.values():
        assert ours == pytest.approx(theirs, rel=1e-5)
    assert abs(losses["published"][0] - losses["swapped"][0]) > 1e-5
    ids = batch["input_ids"][:1]
    shuffled = jnp.concatenate([ids[:, :-1][:, ::-1], ids[:, -1:]], axis=1)

    def last(kind, x):
        c = dataclasses.replace(config, num_hidden_layers=1,
                                layer_types=(kind,), sliding_window=64)
        return mellum.Mellum(c).apply({"params": params}, x)[0][0, -1]

    with jax.default_matmul_precision("highest"):
        for kind in (mellum.FULL, mellum.WINDOW):
            assert float(jnp.abs(last(kind, ids)
                                 - last(kind, shuffled)).max()) > 1e-3


@pytest.mark.parametrize("side", ["program", "reference"])
def test_the_windows_edge_is_at_1023_keys_back(side):
    """One window layer at the published window of 1,024 keys: the state at
    position t moves with the token at t - 1,023 and does not with the one
    at t - 1,024, in the program and in the reference."""
    config = mellum.MellumConfig.small_test(
        dtype=jnp.float32, num_hidden_layers=1,
        layer_types=(mellum.WINDOW,), sliding_window=1024, attention="xla",
        num_attention_heads=2, num_key_value_heads=1)
    model, params = mellum.init_params(config, jax.random.PRNGKey(1))
    t = 1100
    ids = jnp.asarray(_tokens(9, config.vocab_size, 1, 1280)[:, :-1])
    m = _as_reference(config)

    def state(x):
        with jax.default_matmul_precision("highest"):
            if side == "program":
                return model.apply({"params": params}, x)[0][0, t]
            return REFERENCE.hidden_states(params, x, m=m)[0, t]

    base = state(ids)
    bump = lambda at: ids.at[0, at].set((ids[0, at] + 1) % config.vocab_size)
    assert float(jnp.abs(state(bump(t - 1023)) - base).max()) > 1e-6
    np.testing.assert_array_equal(state(bump(t - 1024)), base)


def test_recomputation_changes_no_value_and_keeps_the_kernels_output(
        monkeypatch):
    """With ``remat`` the gradient is the same to the bit; on a TPU (where
    ``auto`` is the kernel at head width 128) a step's jaxpr holds one
    forward and one backward call a layer, windowed in the three window
    layers, and no forward call again: ``ops.remat.remat_policy``. Where a head is
    several blocks of keys (the ``model_results`` boundary, the cell's) the
    heads' norm and the rotation are ``ops/rotary.py``'s kernels, of which
    nothing is kept: ``head_rotary_fwd`` for q and for k a layer, run and
    recomputed, ``head_rotary_bwd`` once for each, the flash calls as
    before."""
    from ray_tpu.ops import flash_kernels

    config, model, params, batch = _small()
    grad = lambda c: jax.jit(jax.grad(lambda p: mellum.loss_fn(
        p, mellum.Mellum(c), batch)[0]))
    plain = grad(config)(params)
    kept = grad(dataclasses.replace(config, remat=True))(params)
    for a, b in zip(jax.tree.leaves(kept), jax.tree.leaves(plain)):
        np.testing.assert_array_equal(a, b)
    wide = mellum.MellumConfig.small_test(
        head_dim=128, sliding_window=128, remat=True, attention="auto")
    # made off the "TPU": the initialiser runs the model, and the expert
    # layer's unwritten buffers are a TPU kernel's
    _, wide_params = mellum.init_params(wide, jax.random.PRNGKey(0))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    def step_calls(seq):
        ids = jnp.zeros((1, seq), jnp.int32)
        return kernel_calls(jax.make_jaxpr(jax.grad(lambda p: mellum.loss_fn(
            p, mellum.Mellum(wide), {"input_ids": ids, "labels": ids})[0]))(
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
    calls.pop("unwritten")
    assert calls == {
        "flash_fwd_w128": 3, "flash_bwd_w128": 3, "flash_fwd": 1,
        "flash_bwd": 1}


# ----------------------------------------------------------------------
# the expert layer, as this model calls it
# ----------------------------------------------------------------------

def _expert_layer(index, of):
    return mla_moe.RoutedExperts(
        experts=8, expert_shard=(index, of), width=16, per_token=3,
        scale=1.0, normalize=True, shared=0, dtype=jnp.float32,
        kernel_init=jax.nn.initializers.normal(0.2), eps=0.0,
        score="softmax")


_ROUTED = {"num_experts_per_tok": 3, "norm_topk_prob": True}


@pytest.mark.parametrize("of", [2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(of):
    """The routed parts of all ``of`` shares (there is no shared expert to
    count once) are the uncut reference's layer; each share is the
    reference's share; every pair fell on exactly one share."""
    keys = jax.random.split(jax.random.PRNGKey(4), 2)
    x = jax.random.normal(keys[0], (2, 48, 32))
    whole = _expert_layer(0, 1).init(keys[1], x)["params"]
    assert set(whole) == {"router", "router_bias", "experts_wi",
                          "experts_wo"}

    def share(index):
        held = 8 // of
        rows = slice(index * held, (index + 1) * held)
        return {**whole, "experts_wi": whole["experts_wi"][rows],
                "experts_wo": whole["experts_wo"][rows]}

    with jax.default_matmul_precision("highest"):
        parts = [_expert_layer(i, of).apply({"params": share(i)}, x)
                 for i in range(of)]
        uncut = REFERENCE._experts(
            x, whole, {**_ROUTED, "expert_shard": {"index": 0, "of": 1}})
        np.testing.assert_allclose(sum(y for y, _ in parts), uncut,
                                   rtol=2e-4, atol=2e-5)
        assert sum(int(n.sum()) for _, n in parts) == 2 * 48 * 3
        for i in (0, of - 1):
            np.testing.assert_allclose(
                parts[i][0], REFERENCE._experts(
                    x, share(i),
                    {**_ROUTED, "expert_shard": {"index": i, "of": of}}),
                rtol=2e-4, atol=2e-5)


def test_no_pair_is_dropped_when_every_token_chooses_held_experts():
    """A selection bias that puts every token's three experts among the
    four held here: the share receives all tokens x 3 pairs, every one
    computed (the routed part is the uncut layer's, to which the absent
    experts add nothing)."""
    keys = jax.random.split(jax.random.PRNGKey(6), 2)
    x = jax.random.normal(keys[0], (2, 48, 32))
    whole = _expert_layer(0, 1).init(keys[1], x)["params"]
    bias = jnp.zeros((8,)).at[4:].set(10.0)    # share 1 of 2 holds 4..7
    whole = {**whole, "router_bias": bias}
    mine = {**whole, "experts_wi": whole["experts_wi"][4:],
            "experts_wo": whole["experts_wo"][4:]}
    with jax.default_matmul_precision("highest"):
        y, tokens = _expert_layer(1, 2).apply({"params": mine}, x)
        uncut = REFERENCE._experts(
            x, whole, {**_ROUTED, "expert_shard": {"index": 0, "of": 1}})
    assert int(tokens.sum()) == 2 * 48 * 3
    np.testing.assert_allclose(y, uncut, rtol=2e-4, atol=2e-5)
    other, none = _expert_layer(0, 2).apply(
        {"params": {**whole, "experts_wi": whole["experts_wi"][:4],
                    "experts_wo": whole["experts_wo"][:4]}}, x)
    assert not int(none.sum()) and not np.asarray(other).any()


def test_the_step_is_the_one_builder_and_reports_the_experts_load():
    """``mellum.build_train_step`` is ``parallel.build_train_step`` with the
    auxiliary output; the selection bias stays where it was initialised; a
    loop's report carries the loss and the experts' load, and the step
    observatory gets them as one ``train/step_aux`` record; a traced pass
    says what the stack holds."""
    config, model, params, batch = _small(expert_shard=(0, 2))
    tx = mellum.make_optimizer()
    pairs = batch["input_ids"].size * config.num_experts_per_tok
    step = mellum.build_train_step(model, tx, donate=False)
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        new_params, _, loss, tokens = step(params, tx.init(params), batch)
        metrics = mellum.step_metrics(loss, tokens, pairs=pairs)
        records = [r for r in steptrace.snapshot()
                   if r["kind"] == "counters"]
    finally:
        steptrace.set_enabled(False)
    assert tokens.shape == (4, 4)
    assert not np.asarray(
        new_params["layers_1"]["moe"]["router_bias"]).any()
    assert np.asarray(new_params["layers_1"]["moe"]["router"] !=
                      params["layers_1"]["moe"]["router"]).any()
    assert set(metrics) == {"loss", "expert_tokens_max", "expert_tokens_mean",
                            "rows_present", "rows_buffered", "rows_fill"}
    assert metrics["rows_present"] == int(np.asarray(tokens).sum())
    assert metrics["expert_tokens_mean"] == pytest.approx(
        metrics["rows_present"] / 16)
    by_name = {}
    for r in records:
        by_name.setdefault(r["name"], []).append(r["values"])
    assert by_name["train/step_aux"] == [metrics]
    assert by_name["model/layer_kinds"][-1] == {
        "sliding_attention": 3, "full_attention": 1, "expert": 4,
        "layers": 4, "published_layers": 28}
    assert {r["kind"] for r in by_name["rope/table"]} == {"plain", "yarn"}


def test_the_familys_step_is_the_workers_and_fills_the_ring():
    """The benchmark's family hands the worker a step that returns the loss
    third and last, lowered and compiled as the worker lowers and compiles
    it; every call leaves one ``train/step_aux`` record. The learning rate
    climbs from 0: the first step moves no weight, the second does. After
    each step the held experts' entries of every layer's selection bias
    have moved by ``selection_bias.update_rate`` against the load that step
    reported; the other experts' entries stay zero."""
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
                         for i in range(4)])

    load = np.asarray(built.loss_with_parts(params, batch)[1][
        "tokens_per_expert"])
    before = biases(params)
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        heads = [np.asarray(params["lm_head"])]
        out = compiled(params, opt_state, batch)
        after = biases(out[0])
        heads.append(np.asarray(out[0]["lm_head"]))
        out = built.step(*out[:2], batch)
        heads.append(np.asarray(out[0]["lm_head"]))
        records = [r["values"] for r in steptrace.snapshot()
                   if r["kind"] == "counters"
                   and r["name"] == "train/step_aux"]
    finally:
        steptrace.set_enabled(False)
    np.testing.assert_allclose(
        after[:, mine] - before[:, mine],
        TOY["train"]["selection_bias"]["update_rate"]
        * np.sign(share - load), atol=1e-7)
    rest = after.copy()
    rest[:, mine] = 0
    assert not rest.any()
    assert len(out) == 3 and np.ndim(out[2]) == 0
    assert len(records) == 2
    for r in records:
        assert r["rows_present"] > 0 and r["expert_tokens_mean"] > 0
        assert r["loss"] == pytest.approx(
            float(np.log(TOY["vocab_size"])), rel=0.02)
    np.testing.assert_array_equal(heads[1], heads[0])
    assert (heads[2] != heads[1]).any()


@pytest.mark.parametrize("seed", [0, 2147483777])
def test_a_run_starts_with_the_held_experts_level_on_its_batch(seed):
    """``make_state`` of the benchmark's family: the weights are the
    program's own from the key, but for the held experts' entries of each
    layer's selection bias, which are moved (``train.selection_bias``)
    until each held expert receives its uniform share of the cell's one
    batch, made again from the seed as ``run.py`` makes it."""
    from perfbench import traffic as traffic_mod

    built = FAMILY.build(TOY, TRAFFIC, None)
    key = jax.random.PRNGKey(seed % 2**32)
    params, _ = jax.jit(built.make_state)(key)
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
    assert moved == TOY["num_hidden_layers"]
    tokens = traffic_mod.resident_tokens(seed, TRAFFIC, TOY["vocab_size"])
    batch = {"input_ids": jnp.asarray(tokens[:, :-1]),
             "labels": jnp.asarray(tokens[:, 1:])}
    share = 4 * 64 * TOY["num_experts_per_tok"] / TOY["num_experts_published"]
    parts = jax.jit(built.loss_with_parts)
    load = np.asarray(parts(params, batch)[1]["tokens_per_expert"])
    unlevelled = np.asarray(parts(plain, batch)[1]["tokens_per_expert"])
    assert load.shape == unlevelled.shape == (moved, held)
    assert np.abs(load - share).max() <= 0.08 * share
    assert np.abs(unlevelled - share).max() > 0.15 * share


# ----------------------------------------------------------------------
# the configuration file
# ----------------------------------------------------------------------

def test_the_configuration_holds_the_published_widths():
    published = {
        "hidden_size": 2304, "num_attention_heads": 32,
        "num_key_value_heads": 4, "head_dim": 128, "intermediate_size": 7168,
        "moe_intermediate_size": 896, "num_experts_published": 64,
        "num_experts_per_tok": 8, "norm_topk_prob": True,
        "sliding_window": 1024, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": False, "attention_bias": False,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "use_sliding_window": True, "hidden_act": "silu",
        "model_type": "mellum"}
    assert {k: CELL[k] for k in published} == published
    assert CELL["rope_parameters"] == {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
    assert CELL["published"] == {"num_hidden_layers": 28, "num_experts": 64,
                                 "vocab_size": 98304}
    cut = {"num_hidden_layers": 4, "num_experts": 16,
           "vocab_size": 98304 // 4}
    assert {k: CELL[k] for k in cut} == cut
    assert set(CELL["reduced_note"]) == set(cut)
    bench = _json("BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "mellum2-12b-a2.5b")
    assert entry["reduced"] == list(cut) and entry["source"] == CELL["source"]
    assert CELL["expert_shard"] == {"index": 0, "of": 4}
    assert "4 v5e chips share each layer" in CELL["deployment"]
    # both published lists, whole; the layers run are the first four: one
    # whole period, every one sparse
    assert len(CELL["layer_types"]) == 28 and all(
        kind == ("full_attention" if (i + 1) % 4 == 0
                 else "sliding_attention")
        for i, kind in enumerate(CELL["layer_types"]))
    assert CELL["mlp_layer_types"] == ["sparse"] * 28
    assert FAMILY.layer_types(CELL) == ("sliding_attention",) * 3 + (
        "full_attention",)
    assert {"qk_norm", "rotary", "block", "attention", "routed",
            "router_bias", "auxiliary_balance_loss", "prediction_module",
            "initializer_range", "optimizer"} <= set(CELL["assumed"])
    # every number of the catalog's row under its key, but the three cut
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(line) for line in f
                       if '"Mellum2-12B-A2.5B-Instruct"' in line)
        assert row["source_url"] == CELL["source"]
        assert {k for k, v in row["config"].items()
                if CELL.get(k) != v} == set(cut)


def test_the_familys_count_is_the_state_the_program_makes():
    built = FAMILY.build(CELL, {"remat": True, "batch": 2, "seq": 8192},
                         None)
    params, _ = jax.eval_shape(built.make_state, jax.random.PRNGKey(0))
    made = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    assert FAMILY.num_params(CELL) == made == 595_154_432
    layer = params["layers_3"]
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(layer)) \
        == 120_476_480
    assert layer["attn"]["q_proj"]["kernel"].shape == (2304, 32 * 128)
    assert layer["attn"]["k_proj"]["kernel"].shape == (2304, 4 * 128)
    assert layer["attn"]["o_proj"]["kernel"].shape == (32 * 128, 2304)
    assert layer["attn"]["q_norm"]["scale"].shape == (128,)
    assert set(layer["attn"]) == {"q_proj", "k_proj", "v_proj", "o_proj",
                                  "q_norm", "k_norm"}
    assert set(layer) == {"attn", "input_norm", "post_attn_norm", "moe"}
    assert layer["moe"]["router"].shape == (2304, 64)
    assert layer["moe"]["experts_wi"].shape == (16, 2304, 2 * 896)
    assert layer["moe"]["experts_wo"].shape == (16, 896, 2304)
    assert params["embed"]["embedding"].shape == (24576, 2304)
    assert params["lm_head"].shape == (24576, 2304)
    # ISSUE 62's arithmetic at 2 x 8,192, forward multiply-adds a token:
    # projections 85M, held experts 50M (two of a token's eight), the head
    # 57M; attention's pairs 57M
    sizes = FAMILY._sizes(CELL)
    assert 4 * sizes["attn"] == pytest.approx(85e6, rel=0.01)
    assert 4 * 2 * sizes["expert"] == pytest.approx(50e6, rel=0.01)
    assert sizes["table"] == pytest.approx(57e6, rel=0.01)
    pairs = FAMILY.attended_pairs_per_token(CELL, 8192)
    assert pairs * 8192 == 33_558_528 + 3 * 7_864_832
    assert 32 * 2 * 128 * pairs == pytest.approx(57e6, rel=0.01)
    flops = FAMILY.train_flops_per_token(CELL, 8192)
    assert flops == pytest.approx(
        6 * FAMILY.matmul_params_per_token(CELL) + 6 * 32 * 256 * pairs)
    assert flops * 2 * 8192 == pytest.approx(24.5e12, rel=0.01)
    bad = dict(CELL, layer_types=["full_attention"] * 28)
    with pytest.raises(ValueError):
        FAMILY.layer_types(bad)

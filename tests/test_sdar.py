"""The block-diffusion model (``ray_tpu.models.sdar``), held to the plain
reference ``perfbench/families/sdar_reference.py`` at small sizes on the
CPU, seeded weights, no cluster: the noise, the two streams under the mask,
the weighted loss and every gradient leaf, all experts held and one share of
four; what does not leak between the streams; the shares of a layer against
the uncut layer; the loss walk under weights and a given denominator; the
configuration file held to the published widths; the benchmark family's step
as the worker calls it."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import compare, worker
from ray_tpu._private import steptrace
from ray_tpu.models import mla_moe, sdar
from ray_tpu.ops import xent
from ray_tpu.parallel import train_step

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


TOY = _json("perfbench", "tests", "configs", "tiny-sdar.json")
CELL = _json("perfbench", "configs", "sdar-30b-a3b-chat.json")
REFERENCE = worker.load_reference(ROOT, TOY)
FAMILY = worker.load_family(ROOT, TOY)
TRAFFIC = {"batch": 4, "seq": 64, "remat": True}


def _tokens(seed, vocab=TOY["vocab_size"], batch=4, seq=64):
    return np.random.default_rng(seed).integers(
        0, vocab, (batch, seq + 1), dtype=np.int32)


def _differences(dtype, seed=3, round_weights=False):
    """The comparison the benchmark's worker makes, in small: the first
    step's loss and its gradient (from Adam's first moment) against the
    float32 reference -> (loss, gradient norm: relative; cosine)."""
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


def test_a_bfloat16_step_is_nearer_than_one_in_a_lower_precision():
    """bfloat16 against float32 at the toy size, and the control (weights
    kept to 3 bits of mantissa): the cosine tells them apart."""
    sound = _differences("bfloat16")
    control = _differences("bfloat16", round_weights=True)
    assert sound[2] >= 0.9995 > control[2], (sound, control)
    assert sound[0] <= 5e-4, sound


# ----------------------------------------------------------------------
# the model against the reference: noise, logits, loss, gradient
# ----------------------------------------------------------------------

def _small(seq=32, batch=2, **kw):
    config = sdar.SdarConfig.small_test(dtype=jnp.float32, noise_seed=7, **kw)
    model, params = sdar.init_params(config, jax.random.PRNGKey(1))
    # norms' scales away from one, so that a misplaced norm shows
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 64))
    params = jax.tree.map(
        lambda x: x + 0.1 * jax.random.normal(next(keys), x.shape)
        if x.ndim == 1 and x.shape[0] != config.num_experts else x, params)
    tokens = _tokens(5, config.vocab_size, batch, seq)
    return config, model, params, {"input_ids": jnp.asarray(tokens[:, :-1]),
                                   "labels": jnp.asarray(tokens[:, 1:])}


def _as_reference(config):
    """The configuration file's keys for the program's ``config``."""
    index, of = config.expert_shard
    keys = ("vocab_size", "hidden_size", "num_hidden_layers",
            "num_attention_heads", "num_key_value_heads", "head_dim",
            "moe_intermediate_size", "num_experts_per_tok", "norm_topk_prob",
            "rope_theta", "rms_norm_eps", "block_length", "noise_eps",
            "noise_seed", "mask_token_id")
    return {**{k: getattr(config, k) for k in keys},
            "expert_shard": {"index": index, "of": of}}


@pytest.mark.parametrize("count", [0, 1, 5])
def test_the_noise_is_the_references_and_every_step_draws_anew(count):
    """Same file and step: the same draw in program and reference (the
    reference's own ``jax.random`` calls); the next step: another. A block
    has one rate; the rates are stratified over the step's blocks; a masked
    position reads the MASK row; about half of the positions are masked."""
    config, _, _, batch = _small(seq=64, batch=4)
    clean = batch["input_ids"]
    noisy, masked, p = sdar.noise(config, clean, jnp.int32(count))
    theirs_noisy, weights = REFERENCE.noised(_as_reference(config), clean,
                                             count)
    np.testing.assert_array_equal(noisy, theirs_noisy)
    np.testing.assert_allclose(weights, masked / p, rtol=1e-6)
    np.testing.assert_array_equal(
        np.asarray(noisy), np.where(masked, config.mask_token_id, clean))
    other = sdar.noise(config, clean, jnp.int32(count + 1))
    assert (np.asarray(other[1]) != np.asarray(masked)).mean() > 0.2
    again = sdar.noise(config, clean, jnp.int32(count))
    np.testing.assert_array_equal(again[1], masked)
    rates = np.asarray(p).reshape(4, 16, 4)
    np.testing.assert_array_equal(rates, rates[..., :1].repeat(4, -1))
    t = np.sort((rates[..., 0].ravel() - config.noise_eps)
                / (1 - config.noise_eps))
    assert 0 < t[0] and t[-1] <= 1
    np.testing.assert_allclose(np.diff(t), 1 / 64, atol=1e-5)  # one a stratum
    assert 0.3 < float(masked.mean()) < 0.7


@pytest.mark.parametrize("shard", [(0, 1), (1, 4)], ids=["all", "one_of_4"])
def test_the_model_is_the_reference(shard):
    """Logits of the noisy stream, the loss and every parameter's gradient,
    float32 on both sides: two layers, two key-value heads for four query
    heads, blocks of 4 in 32 positions, every expert held and one share of
    four."""
    config, model, params, batch = _small(expert_shard=shard)
    m = _as_reference(config)
    clean = batch["input_ids"]
    noisy, masked, p = sdar.noise(config, clean, 0)
    both = jnp.concatenate([noisy, clean], axis=1)
    with jax.default_matmul_precision("highest"):
        hidden, tokens = model.apply({"params": params}, both)
        ours = hidden @ params["lm_head"].T
        theirs = REFERENCE.logits(params, both, m=m)
        assert ours.shape == theirs.shape == (2, 32, config.vocab_size)
        np.testing.assert_allclose(ours, theirs, atol=2e-5, rtol=2e-4)
        (loss, aux), grads = jax.value_and_grad(
            lambda p_: sdar.loss_fn(p_, model, batch, 0), has_aux=True)(params)
    assert tokens.shape == (2, config.experts_held)
    assert float(aux["masked_share"]) == pytest.approx(float(masked.mean()))
    ref_loss, ref_grads = REFERENCE.make(m, True)(
        params, *REFERENCE.noised(m, clean)[:1], clean,
        REFERENCE.noised(m, clean)[1])
    assert float(loss) == pytest.approx(float(ref_loss), rel=1e-5)
    # by hand: the weighted log-likelihood of the own token, over B L
    log_p = jax.nn.log_softmax(theirs, axis=-1)
    own = jnp.take_along_axis(log_p, clean[..., None], axis=-1)[..., 0]
    assert float(loss) == pytest.approx(
        float(-(own * masked / p).sum() / clean.size), rel=1e-5)
    flat = jax.tree_util.tree_leaves_with_path(grads)
    assert len(flat) == len(jax.tree.leaves(ref_grads))
    for (path, a), b in zip(flat, jax.tree.leaves(ref_grads)):
        name = jax.tree_util.keystr(path)
        if name.endswith("['router_bias']"):
            assert not np.asarray(a).any()      # takes a zero gradient
            continue
        scale = float(jnp.abs(b).max())
        assert scale > 0, name
        np.testing.assert_allclose(a, b, atol=2e-4 * scale, rtol=2e-3,
                                   err_msg=name)


def test_nothing_leaks_between_the_streams():
    """Through the whole model: the noisy stream's hidden states in block b
    do not move when clean tokens of blocks >= b or noisy tokens of other
    blocks change; they do move with a clean token before b and with a
    noisy token of b itself."""
    config, model, params, batch = _small()
    clean = np.asarray(batch["input_ids"])
    noisy = np.asarray(sdar.noise(config, jnp.asarray(clean), 0)[0])
    length, block, b = 32, config.block_length, 5
    mine = slice(b * block, (b + 1) * block)
    run = jax.jit(lambda n, c: model.apply(
        {"params": params}, jnp.concatenate([n, c], axis=1))[0])
    base = run(noisy, clean)
    other = (clean + 17) % (config.vocab_size - 1)
    at = np.arange(length)
    elsewhere, from_b = at // block != b, at // block >= b
    moved = run(np.where(elsewhere, other, noisy),
                np.where(from_b, other, clean))
    np.testing.assert_array_equal(base[:, mine], moved[:, mine])
    before = run(noisy, np.where(at == b * block - 1, other, clean))
    assert not np.allclose(base[:, mine], before[:, mine])
    inside = run(np.where(at == b * block, other, noisy), clean)
    assert not np.allclose(base[:, mine], inside[:, mine])


def test_recomputation_changes_no_value():
    config, model, params, batch = _small()
    again = sdar.Sdar(dataclasses.replace(config, remat=True))
    fn = lambda net: jax.value_and_grad(
        lambda p: sdar.loss_fn(p, net, batch, 3)[0])(params)
    (a, ga), (b, gb) = fn(model), fn(again)
    assert float(a) == pytest.approx(float(b), rel=1e-6)
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_allclose(x, y, atol=1e-6, rtol=1e-5)


# ----------------------------------------------------------------------
# the loss walk under weights and a denominator that is not their sum
# ----------------------------------------------------------------------

@pytest.mark.parametrize("chunks", [1, 4])
def test_chunked_xent_takes_weights_and_a_given_denominator(chunks):
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    hidden = jax.random.normal(ks[0], (2, 16, 8))
    head = jax.random.normal(ks[1], (32, 8))
    labels = jax.random.randint(ks[2], (2, 16), 0, 32)
    weights = jnp.where(jax.random.uniform(ks[3], (2, 16)) < 0.5, 0.0,
                        1.0 + jax.random.uniform(ks[3], (2, 16)) * 9)

    def walked(h, e):
        return xent.chunked_xent(h, e, labels, weights, n_chunks=chunks,
                                 denom=labels.size)

    def whole(h, e):
        ll = xent.token_log_likelihood(h @ e.T, labels)
        return -(ll * weights).sum() / labels.size

    np.testing.assert_allclose(walked(hidden, head), whole(hidden, head),
                               rtol=1e-6)
    for a, b in zip(jax.grad(walked, (0, 1))(hidden, head),
                    jax.grad(whole, (0, 1))(hidden, head)):
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5)
    # left out, the denominator is the weights' sum, as it was: with 0/1
    # weights ``fused_xent``'s masked mean
    mask = (weights > 0).astype(jnp.float32)
    np.testing.assert_allclose(
        xent.chunked_xent(hidden, head, labels, mask, n_chunks=chunks),
        xent.fused_xent(hidden @ head.T, labels, mask), rtol=1e-6)


# ----------------------------------------------------------------------
# the shares of the deployment
# ----------------------------------------------------------------------

@pytest.mark.parametrize("of", [2, 4, 8])
def test_the_shares_add_up_to_the_uncut_layer(of):
    """The guide's test: one expert layer, its ``of`` shares' parts summed,
    is the uncut layer's output; every share's router is the whole one."""
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 16, 32))

    def layer(index, of):
        return mla_moe.RoutedExperts(
            experts=8, expert_shard=(index, of), width=16, per_token=3,
            scale=1.0, normalize=True, shared=0, dtype=jnp.float32,
            kernel_init=jax.nn.initializers.normal(0.2), eps=0.0,
            score="softmax")

    whole = layer(0, 1)
    params = whole.init(jax.random.PRNGKey(1), x)["params"]
    want, tokens = whole.apply({"params": params}, x)
    held = 8 // of
    total, loads = 0, []
    for index in range(of):
        mine = slice(index * held, (index + 1) * held)
        part = {**params, "experts_wi": params["experts_wi"][mine],
                "experts_wo": params["experts_wo"][mine]}
        y, n = layer(index, of).apply({"params": part}, x)
        total, loads = total + y, loads + [n]
    np.testing.assert_allclose(total, want, atol=1e-5, rtol=1e-5)
    np.testing.assert_array_equal(jnp.concatenate(loads), tokens)
    assert int(tokens.sum()) == 2 * 16 * 3       # no pair dropped


# ----------------------------------------------------------------------
# the step: the one builder, the optimizer's count as its clock
# ----------------------------------------------------------------------

def test_the_step_is_the_one_builder_and_draws_by_the_optimizers_count():
    config, model, params, batch = _small()
    tx = sdar.make_optimizer(1e-3)
    opt_state = tx.init(params)
    assert int(train_step.step_count(opt_state)) == 0
    step = sdar.build_train_step(model, tx, donate=False)
    assert isinstance(step, train_step._StepByLayout)
    p1, o1, loss0, share0, tokens = step(params, opt_state, batch)
    assert int(train_step.step_count(o1)) == 1
    assert tokens.shape == (2, config.experts_held)
    want0 = sdar.loss_fn(params, model, batch, 0)
    assert float(loss0) == pytest.approx(float(want0[0]), rel=1e-6)
    assert float(share0) == float(want0[1]["masked_share"])
    _, _, loss1, share1, _ = step(p1, o1, batch)
    want1 = sdar.loss_fn(p1, model, batch, 1)
    assert float(loss1) == pytest.approx(float(want1[0]), rel=1e-6)
    assert float(share1) == float(want1[1]["masked_share"]) != float(share0)
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        pairs = 2 * 2 * 32 * config.num_experts_per_tok
        metrics = sdar.step_metrics(loss1, share1, tokens, pairs=pairs)
        records = [r for r in steptrace.snapshot()
                   if r["kind"] == "counters" and r["name"] == "train/step_aux"]
    finally:
        steptrace.set_enabled(False)
    assert records[-1]["values"] == metrics
    assert metrics["masked_share"] == float(share1)
    # every expert held: each of the two layers holds every pair
    assert metrics["rows_present"] == 2 * pairs
    assert metrics["expert_tokens_mean"] == pairs / 8


def test_the_familys_step_is_the_workers_and_fills_the_ring():
    """``build`` hands the worker a step with the loss third and last, whose
    ``lower().compile()`` runs; the ring holds ``model/layer_kinds``, the
    ``attention/boundary`` record of the ``jnp`` path and ``train/step_aux``
    with ``masked_share``."""
    built = FAMILY.build(TOY, TRAFFIC, None)
    params, opt_state = jax.jit(built.make_state)(jax.random.PRNGKey(0))
    tokens = _tokens(0)
    batch = {"input_ids": jnp.asarray(tokens[:, :-1]),
             "labels": jnp.asarray(tokens[:, 1:])}
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        compiled = built.step.lower(params, opt_state, batch).compile()
        params, opt_state, loss = compiled(params, opt_state, batch)
        records = [r for r in steptrace.snapshot() if r["kind"] == "counters"]
    finally:
        steptrace.set_enabled(False)
    assert isinstance(loss, float) and np.isfinite(loss)
    by_name = {r["name"]: r["values"] for r in records}
    assert by_name["model/layer_kinds"] == {
        "block_diffusion": 2, "expert": 2, "layers": 2,
        "published_layers": 48, "block_length": 4, "streams": 2}
    assert by_name["attention/boundary"] | {"heads": 0} == {
        "tokens": 128, "heads": 0, "kv_heads": 2, "d_qk": 16, "d_v": 16,
        "window": 0, "heads_a_lane_tile": 0, "model_arrays": 0,
        "model_results": 0, "blocks": 4, "kernel": 0, "live_blocks": 0,
        "skipped_blocks": 0}
    aux = by_name["train/step_aux"]
    assert aux["loss"] == loss and 0.3 < aux["masked_share"] < 0.7
    positions = 2 * 4 * 64
    assert aux["rows_present"] <= positions * TOY["num_experts_per_tok"]
    assert aux["expert_tokens_mean"] == pytest.approx(
        positions * 3 / 8, rel=0.15)     # levelled: near the uniform share


# ----------------------------------------------------------------------
# the configuration file and the family's counts
# ----------------------------------------------------------------------

def test_the_configuration_holds_the_published_widths():
    published = {
        "hidden_size": 2048, "num_attention_heads": 32,
        "num_key_value_heads": 4, "head_dim": 128, "intermediate_size": 6144,
        "moe_intermediate_size": 768, "num_experts_published": 128,
        "num_experts_per_tok": 8, "norm_topk_prob": True,
        "rope_theta": 1000000, "rope_scaling": None, "rms_norm_eps": 1e-6,
        "tie_word_embeddings": False, "attention_bias": False,
        "decoder_sparse_step": 1, "mlp_only_layers": [],
        "use_sliding_window": False, "sliding_window": None,
        "max_position_embeddings": 32768, "hidden_act": "silu",
        "model_type": "sdar_moe"}
    assert {k: CELL[k] for k in published} == published
    assert CELL["published"] == {"num_hidden_layers": 48, "num_experts": 128,
                                 "vocab_size": 151936}
    cut = {"num_hidden_layers": 6, "num_experts": 16,
           "vocab_size": 151936 // 8}
    assert {k: CELL[k] for k in cut} == cut
    assert set(CELL["reduced_note"]) == set(cut)
    bench = _json("BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "sdar-30b-a3b-chat")
    assert entry["reduced"] == list(cut) and entry["source"] == CELL["source"]
    assert CELL["expert_shard"] == {"index": 0, "of": 8}
    assert "8 v5e chips share each layer" in CELL["deployment"]
    assert {"qk_norm", "block_length", "schedule", "no_shift", "mask_token",
            "noise_seed", "initializer_range", "auxiliary_balance_loss",
            "router_bias", "optimizer"} <= set(CELL["assumed"])
    assert (CELL["block_length"], CELL["noise_eps"]) == (4, 1e-3)
    assert CELL["mask_token_id"] == CELL["vocab_size"] - 1
    # every number of the catalog's row under its key, but the three cut
    catalog = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(catalog):
        with open(catalog) as f:
            row = next(json.loads(line) for line in f
                       if '"SDAR-30B-A3B-Chat"' in line)
        assert row["source_url"] == CELL["source"]
        assert {k for k, v in row["config"].items()
                if CELL.get(k) != v} == set(cut)
    traffic = _json("perfbench", "traffic", "step-bd-4k.json")
    assert {k: traffic[k] for k in (
        "batch", "seq", "feed", "remat", "save_every_steps", "warmup_steps",
        "traced_steps")} == {
        "batch": 2, "seq": 4096, "feed": "resident", "remat": True,
        "save_every_steps": 0, "warmup_steps": 3, "traced_steps": 8}


def test_the_familys_counts_are_the_programs_and_a_hand_count():
    built = FAMILY.build(CELL, {"remat": True, "batch": 2, "seq": 4096},
                         None)
    params, _ = jax.eval_shape(built.make_state, jax.random.PRNGKey(0))
    made = sum(int(np.prod(x.shape)) for x in jax.tree.leaves(params))
    layer = (2 * 8_388_608 + 2 * 1_048_576 + 256 + 4_096 + 262_144 + 128
             + 16 * 4_718_592)
    assert layer == 94_638_464
    assert FAMILY.num_params(CELL) == made == 645_624_064 == (
        6 * layer + 2 * 38_895_616 + 2_048)
    # operations a DATA token: both streams through six layers (q, o, k, v,
    # the router, one expert of the eight a position takes: 16 of 128
    # held), the head over the noisy stream, and L + D pairs a head a layer
    a_position = 2 * 8_388_608 + 2 * 1_048_576 + 262_144 + 4_718_592
    matmuls = 6 * (38_895_616 + 2 * 6 * a_position)
    pairs = 4096 * 4096 + 4096 * 4
    assert FAMILY.live_pairs(4096, 4) == pairs == 16_793_600
    attention = 6 * 32 * 2 * 128 * 6 * pairs / 4096
    assert FAMILY.train_flops_per_token(CELL, 4096) == matmuls + attention
    assert attention / (matmuls + attention) == pytest.approx(0.38, abs=0.01)
    # the toy's count is its state's too
    toy = FAMILY.build(TOY, TRAFFIC, None)
    params, _ = jax.eval_shape(toy.make_state, jax.random.PRNGKey(0))
    assert FAMILY.num_params(TOY) == sum(
        int(np.prod(x.shape)) for x in jax.tree.leaves(params))

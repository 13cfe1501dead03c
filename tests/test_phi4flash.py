"""The five-kinds decoder (``ray_tpu.models.phi4flash``), held to the plain
reference ``perfbench/families/phi4flash_reference.py`` at small sizes on
the CPU, seeded weights, no cluster; its configuration file held to the
published widths; the benchmark family's step as the worker calls it."""

import dataclasses
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import compare, worker
from ray_tpu._private import steptrace
from ray_tpu.models import phi4flash
from ray_tpu.ops import attention
from tests.conftest import kernel_calls

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _json(*path):
    with open(os.path.join(ROOT, *path)) as f:
        return json.load(f)


TOY = _json("perfbench", "tests", "configs", "tiny-phi4flash.json")
CELL = _json("perfbench", "configs", "phi-4-mini-flash.json")
REFERENCE = worker.load_reference(ROOT, TOY)
FAMILY = worker.load_family(ROOT, TOY)
TRAFFIC = {"batch": 4, "seq": 64, "remat": True}
KEPT = (0, 1, 4, 5, 6, 7)       # of 8: every kind, both hand-overs


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


# bfloat16 against float32 at the toy size, read on the CPU (seeds 3, 5, 7,
# 11): loss 1.3e-5 to 2.3e-5, gradient norm 1.1e-4 to 9.2e-4, cosine 0.99992
# to 0.99993. The control (weights kept to 3 bits of mantissa, the same
# seeds): loss 6.9e-6 to 1.4e-4, norm 1.5e-4 to 4.2e-3, cosine 0.99808 to
# 0.99850. The cosine tells them apart in every seed (1 - cosine: 8.4e-5 at
# the worst against 1.5e-3 at the best, limit 4e-4); loss and norm overlap
# and stand at about 5x the worst sound reading.
BF16_LIMITS = {"loss": 2e-4, "norm": 5e-3, "cosine": 0.9996}


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

def _small(kept=KEPT, **kw):
    config = phi4flash.Phi4FlashConfig.small_test(
        dtype=jnp.float32, kept_layers=kept, **kw)
    model, params = phi4flash.init_params(config, jax.random.PRNGKey(1))
    # norms' scales and every bias away from their start, so that a
    # misplaced norm or a dropped bias shows
    keys = iter(jax.random.split(jax.random.PRNGKey(2), 256))
    params = jax.tree.map(
        lambda x: x + 0.1 * jax.random.normal(next(keys), x.shape)
        if x.ndim == 1 else x, params)
    # queries and keys large enough for maps that are far from uniform:
    # where both maps are the mean over the keys, lam only scales what the
    # norm after it scales back, and its gradient is rounding
    params = jax.tree_util.tree_map_with_path(
        lambda path, x: 25.0 * x if x.ndim == 2 and any(
            getattr(k, "key", None) in ("q_proj", "k_proj") for k in path)
        else x, params)
    tokens = _tokens(5, config.vocab_size, 2, 32)
    batch = {"input_ids": jnp.asarray(tokens[:, :-1]),
             "labels": jnp.asarray(tokens[:, 1:])}
    return config, model, params, batch


def _as_reference(config):
    """The configuration file's keys for the program's ``config``."""
    return {
        "published": {"num_hidden_layers": config.num_hidden_layers},
        "kept_layers": list(config.kept_layers),
        **{key: getattr(config, key) for key in (
            "hidden_size", "num_attention_heads", "num_key_value_heads",
            "sliding_window", "layer_norm_eps", "d_state", "dt_rank")}}


def _reference_loss_and_grads(config, params, batch):
    return jax.value_and_grad(REFERENCE.loss)(
        params, batch["input_ids"], batch["labels"], m=_as_reference(config))


def _close(got, want, tol=2e-5):
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want, atol=tol * max(scale, 1e-6),
                               rtol=tol)


def _same_gradients(grads, want):
    """Leaf by leaf, to 1e-4 of the leaf's largest entry, over a floor of
    rounding at the tree's scale. A key's bias moves every score of a query
    alike and the softmax not at all: its gradient is rounding on both
    sides; every other leaf is reached."""
    flat, wanted = (jax.tree_util.tree_leaves_with_path(t)
                    for t in (grads, want))
    assert len(flat) == len(wanted)
    whole = max(float(jnp.abs(ref).max()) for _, ref in wanted)
    for (path, got), (_, ref) in zip(flat, wanted):
        name = jax.tree_util.keystr(path)
        scale = float(jnp.abs(ref).max())
        assert "['k_proj']['bias']" in name or scale > 1e-9 * whole, name
        np.testing.assert_allclose(
            got, ref, atol=1e-4 * scale + 1e-8 * whole, rtol=1e-3,
            err_msg=name)


def test_logits_loss_and_gradient_match_the_reference():
    config, model, params, batch = _small()
    m = _as_reference(config)
    hidden = model.apply({"params": params}, batch["input_ids"])
    _close(hidden @ params["embed"]["embedding"].T,
           REFERENCE.logits(params, batch["input_ids"], m=m), 1e-4)
    loss, grads = jax.value_and_grad(phi4flash.loss_fn)(params, model, batch)
    want_loss, want = _reference_loss_and_grads(config, params, batch)
    assert abs(float(loss) - float(want_loss)) <= 1e-5 * float(want_loss)
    _same_gradients(grads, want)


def test_the_published_rule_names_every_layer():
    kinds = phi4flash.layer_kinds(32)
    assert kinds == tuple(CELL["layer_kinds"])
    assert [REFERENCE.layer_kind(i, 32) for i in range(32)] == list(kinds)
    count = {k: kinds.count(k) for k in phi4flash.KINDS}
    assert count == {"ssm": 9, "window": 8, "full": 1, "gmu": 7, "cross": 7}
    assert [kinds[i] for i in CELL["kept_layers"]] == [
        "ssm", "window", "ssm", "full", "gmu", "cross"]
    assert kinds[16] == "ssm" and kinds[17] == "full"    # the hand-overs
    # a memory unit without layer L/2, a cross layer without L/2 + 1
    for kept in ((0, 1, 17, 18), (0, 1, 16, 19)):
        with pytest.raises(AssertionError):
            phi4flash.Phi4FlashConfig(kept_layers=kept)


# one stack a kind of block: the layers that kind needs above it, then it
_STACKS = {"ssm": (0,), "window": (1,), "full": (5,), "gmu": (4, 6),
           "cross": (5, 7)}


@pytest.mark.parametrize("kind", _STACKS)
def test_one_kind_of_block_matches_the_reference(kind):
    config, model, params, batch = _small(_STACKS[kind])
    assert config.kinds[-1][1] == kind
    loss, grads = jax.value_and_grad(phi4flash.loss_fn)(params, model, batch)
    want_loss, want = _reference_loss_and_grads(config, params, batch)
    assert abs(float(loss) - float(want_loss)) <= 1e-5 * float(want_loss)
    _same_gradients(grads, want)


def test_what_is_handed_down_gets_gradient_from_both_readers():
    """Layer L/2's memory is read by its own gate and by the memory unit;
    layer L/2 + 1's keys and values by its own queries and by the cross
    layer's. With a reader's output projection zeroed that reader passes
    no gradient back, so the gradient of what makes M (the state-space
    layer's ``x_proj``) or K and V (the full layer's ``k_proj``,
    ``v_proj``) changes; it is the sum of both where both read."""
    config, model, params, batch = _small()

    def grad_of(params, *path):
        g = jax.grad(phi4flash.loss_fn)(params, model, batch)
        for name in path:
            g = g[name]
        return g

    def without(params, layer):
        mixer = dict(params[layer]["mixer"])
        name = "out_proj" if "out_proj" in mixer else "o_proj"
        mixer[name] = jax.tree.map(jnp.zeros_like, mixer[name])
        return {**params, layer: {**params[layer], "mixer": mixer}}

    for maker, readers, leaves in (
            ("layers_4", ("layers_4", "layers_6"),
             [("x_proj", "kernel")]),
            ("layers_5", ("layers_5", "layers_7"),
             [("k_proj", "kernel"), ("v_proj", "bias")])):
        for leaf in leaves:
            path = (maker, "mixer", *leaf)
            both = grad_of(params, *path)
            alone = [grad_of(without(params, r), *path) for r in readers]
            for one in alone:
                assert float(jnp.abs(both - one).max()) > 1e-3 * float(
                    jnp.abs(both).max()), (path,)


def test_recomputation_changes_no_loss_and_no_gradient():
    config, model, params, batch = _small()
    again = phi4flash.Phi4Flash(dataclasses.replace(config, remat=True))
    loss, grads = jax.value_and_grad(phi4flash.loss_fn)(params, model, batch)
    loss_r, grads_r = jax.value_and_grad(phi4flash.loss_fn)(params, again,
                                                            batch)
    assert float(loss) == float(loss_r)
    for a, b in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_r)):
        np.testing.assert_array_equal(a, b)


def test_a_recomputed_stack_runs_no_forward_kernel_twice(monkeypatch):
    """With the kernels forced (interpreted here), a recomputed stack's
    gradient holds each differential layer's two forward and two backward
    flash calls and each state-space layer's forward and backward scan
    once: the policy keeps their outputs. The window layer's calls are
    named after its window; ``model/layer_kinds`` says what was built."""
    import functools

    from ray_tpu.ops import ssm

    monkeypatch.setattr(attention, "flash_attention", functools.partial(
        attention.flash_attention, impl="pallas_interpret"))
    monkeypatch.setattr(ssm, "auto_impl", lambda x, a: "pallas_interpret")
    config = phi4flash.Phi4FlashConfig.small_test(
        dtype=jnp.float32, kept_layers=KEPT, remat=True, attention="flash",
        hidden_size=128, num_attention_heads=4, sliding_window=16)
    model, params = phi4flash.init_params(config, jax.random.PRNGKey(0))
    tokens = _tokens(1, config.vocab_size, 1, 128)
    batch = {"input_ids": jnp.asarray(tokens[:, :-1]),
             "labels": jnp.asarray(tokens[:, 1:])}
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        jax.clear_caches()
        jaxpr = jax.make_jaxpr(jax.grad(
            lambda p: phi4flash.loss_fn(p, model, batch)))(params)
        kinds = [r["values"] for r in steptrace.snapshot()
                 if r["kind"] == "counters"
                 and r["name"] == "model/layer_kinds"]
    finally:
        steptrace.set_enabled(False)
        jax.clear_caches()
    assert kernel_calls(jaxpr) == {
        "flash_fwd": 4, "flash_bwd": 4, "flash_fwd_w16": 2,
        "flash_bwd_w16": 2, "ssm_scan_fwd": 2, "ssm_scan_bwd": 2}
    assert kinds and kinds[-1] == {
        "ssm": 2, "window": 1, "full": 1, "gmu": 1, "cross": 1, "layers": 6,
        "published_layers": 8, "hands_memory": 4, "hands_keys_values": 5}


# ----------------------------------------------------------------------
# the configuration file
# ----------------------------------------------------------------------

def test_the_cell_is_at_the_published_widths():
    """Every number of the catalog's ``config`` under its own key, but the
    two the file lists as reduced; the state is what the adapter counts."""
    published = {
        "embd_pdrop": 0, "hidden_act": "silu", "hidden_size": 2560,
        "intermediate_size": 10240, "layer_norm_eps": 1e-05,
        "max_position_embeddings": 262144, "mb_per_layer": 2,
        "model_type": "phi4flash", "num_attention_heads": 40,
        "num_hidden_layers": 32, "num_key_value_heads": 20, "resid_pdrop": 0,
        "sliding_window": 512, "tie_word_embeddings": True,
        "mlp_bias": False, "lm_head_bias": False, "vocab_size": 200064}
    reduced = {"num_hidden_layers", "vocab_size"}
    bench = _json("BENCHMARK.json")
    entry = next(c for c in bench["configs"] if c["name"] == "phi-4-mini-flash")
    assert set(entry["reduced"]) == reduced
    for key, value in published.items():
        if key in reduced:
            assert CELL["published"][key] == value and CELL[key] != value
        else:
            assert CELL[key] == value, key
    assert CELL["vocab_size"] == 200064 // 8 == 25008
    assert CELL["num_hidden_layers"] == len(CELL["kept_layers"]) == 6
    assert CELL["dt_rank"] == -(-2560 // 16) == 160
    family = worker.load_family(ROOT, CELL)
    assert family.num_params(CELL) == 697_094_272
    # the program's state at the cell's sizes, by shape alone
    built = family.build(CELL, {"batch": 1, "seq": 16384, "remat": True},
                         None)
    shapes = jax.eval_shape(built.make_state, jax.random.PRNGKey(0))[0]
    assert sum(int(np.prod(x.shape)) for x in jax.tree.leaves(shapes)) \
        == family.num_params(CELL)
    step = 16384 * family.train_flops_per_token(CELL, 16384)
    assert step == pytest.approx(81.3e12, rel=2e-3)

"""The one step builder (``parallel.build_train_step``) under the models that
are not GPT-2, and the one placement rule (``parallel.state_shardings``)
under the four layouts that use it, on the suite's virtual CPU devices.
GPT-2's own step is held in ``tests/test_fsdp_step.py``.
"""

import re

import jax
import numpy as np
import optax
import pytest

from ray_tpu import parallel
from ray_tpu.models import gpt2, llama, moe_lm, vision
from ray_tpu.parallel import mesh_utils

KEY = jax.random.PRNGKey(0)


def _llama():
    config = llama.LlamaConfig.small_test()
    model, params = llama.init_params(config, KEY)
    batch = gpt2.synthetic_batch(KEY, 8, 32, config.vocab_size)
    return (llama, model, params, batch,
            lambda p, b: jax.value_and_grad(llama.loss_fn)(p, model, b))


def _vit():
    config = vision.ViTConfig.small_test()
    model = vision.ViT(config)
    params, _, _ = vision.make_train_state(model, config, KEY)
    batch = vision.synthetic_image_batch(KEY, 8, config.image_size,
                                         config.num_classes)

    def loss_of(params, batch):
        logits = model.apply({"params": params}, batch["image"])
        return vision.classification_loss(logits, batch["label"])

    return vision, model, params, batch, jax.value_and_grad(loss_of)


def _moe_lm():
    config = moe_lm.MoELMConfig.small_test()
    model, params = moe_lm.init_params(config, KEY)
    batch = gpt2.synthetic_batch(KEY, 8, 16, config.vocab_size)
    return (moe_lm, model, params, batch,
            lambda p, b: jax.value_and_grad(moe_lm.loss_fn, has_aux=True)(
                p, model, b, config.aux_loss_coeff))


MODELS = {"llama": _llama, "vit": _vit, "moe_lm": _moe_lm}


def _parent_step(loss_and_grads, tx):
    """A model's ``build_train_step`` as it was before the one builder: the
    plain jit (``moe_lm``'s returned its loss's two parts after the
    loss)."""

    def step(params, opt_state, batch):
        out, grads = loss_and_grads(params, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return (params, opt_state, *jax.tree.leaves(out))

    return jax.jit(step, donate_argnums=(0, 1))


@pytest.mark.parametrize("name", MODELS)
def test_on_one_device_the_step_lowers_to_the_program_it_was(name):
    """What ``_StepByLayout`` gave GPT-2 adds nothing here either: no
    sharding constraint, and the text of the plain jit. The names of the
    results apart: ``moe_lm``'s loss and its two parts leave the jit as one
    subtree (``result[2][0]``, where it was ``result[2]``) and the caller
    flattens them."""
    unnamed = lambda text: re.sub(r'jax\.result_info = "[^"]*"', "", text)
    module, model, params, batch, loss_and_grads = MODELS[name]()
    tx = gpt2.make_optimizer()
    args = (params, tx.init(params), batch)
    text = module.build_train_step(model, tx, donate=True).lower(
        *args).as_text()
    assert "sharding" not in text.lower()
    assert unnamed(text) == unnamed(
        _parent_step(loss_and_grads, tx).lower(*args).as_text())


def _mesh(axes):
    n = int(np.prod(list(axes.values())))
    return parallel.create_mesh(axes, devices=jax.devices()[:n])


def _replicated(params, opt_state, batch):
    mesh = _mesh({"data": 4})
    rep = jax.tree.map(lambda _: mesh_utils.replicated(mesh), params)
    return (*parallel.place_train_state(params, opt_state, rep),
            gpt2.shard_batch(batch, mesh))


def _fsdp(params, opt_state, batch):
    # min_size: the tiny kernels are split too
    mesh = _mesh({"fsdp": 4})
    p_sh = mesh_utils.shard_params_fsdp(params, mesh, min_size=2**10)
    return (*parallel.place_train_state(params, opt_state, p_sh),
            gpt2.shard_batch(batch, mesh))


def _ep(params, opt_state, batch):
    params, opt_state, place_batch = moe_lm.shard_train_state_ep(
        params, opt_state, _mesh({"data": 2, "ep": 4}))
    return params, opt_state, place_batch(batch)


def _shardings(*trees):
    return jax.tree.map(lambda x: x.sharding, trees)


@pytest.mark.parametrize("name,place,spread", [
    ("llama", _replicated, False), ("llama", _fsdp, True),
    ("vit", _replicated, False), ("vit", _fsdp, True),
    ("moe_lm", _replicated, False), ("moe_lm", _ep, True),
], ids=["llama-data4", "llama-fsdp4", "vit-data4", "vit-fsdp4",
        "moe_lm-data4", "moe_lm-data2_ep4"])
def test_on_a_mesh_the_step_compiles_once_and_keeps_its_shardings(
        name, place, spread):
    """What PR 26 gave GPT-2, for every model through the one builder: the
    state comes back in the shardings it went in, so the second and third
    steps find the first one's program."""
    module, model, params, batch, _ = MODELS[name]()
    tx = gpt2.make_optimizer()
    params, opt_state, batch = place(params, tx.init(params), batch)
    before = _shardings(params, opt_state)
    assert spread == any(not s.is_fully_replicated
                         for s in jax.tree.leaves(before))
    step = module.build_train_step(model, tx, donate=False)
    for _ in range(3):
        params, opt_state, loss, *_ = step(params, opt_state, batch)
        assert _shardings(params, opt_state) == before
    assert np.isfinite(float(loss))
    (_, jitted), = step._by_layout
    assert jitted._cache_size() == 1


def _gpt2_state():
    # wide enough that ``shard_params_fsdp`` splits the kernels
    config = gpt2.GPT2Config.small_test(n_embd=256)
    _, params, tx, _ = gpt2.make_train_state(config, KEY)
    return params, tx


def _by_fsdp():
    params, tx = _gpt2_state()
    return params, tx, lambda p, o: gpt2.shard_train_state(
        p, o, _mesh({"fsdp": 4}), fsdp=True)


def _by_tp():
    params, tx = _gpt2_state()
    return params, tx, lambda p, o: gpt2.shard_train_state_tp(
        p, o, _mesh({"data": 2, "model": 2}))


def _by_pipeline():
    config = gpt2.GPT2Config.small_test(n_layer=4)
    params, tx, _ = gpt2.make_pipeline_train_state(config, KEY, n_stages=4)
    return params, tx, lambda p, o: gpt2.shard_pipeline_state(
        p, o, _mesh({"data": 2, "pipeline": 4}))


def _by_ep():
    _, params = moe_lm.init_params(moe_lm.MoELMConfig.small_test(), KEY)
    return params, gpt2.make_optimizer(), lambda p, o: (
        moe_lm.shard_train_state_ep(p, o, _mesh({"data": 2, "ep": 4}))[:2])


@pytest.mark.parametrize("layout", [_by_fsdp, _by_tp, _by_pipeline, _by_ep],
                         ids=["fsdp", "tp", "pipeline", "ep"])
def test_moments_lie_as_their_parameter_and_counters_are_replicated(layout):
    """The four ``shard_*_state*`` functions differ in the rule for the
    parameters alone; the optimizer state follows by ``state_shardings``,
    which says of the abstract state what the placed state then has."""
    params, tx, place = layout()
    abstract = jax.eval_shape(lambda p: (p, tx.init(p)), params)
    params, opt_state = place(params, tx.init(params))
    p_sh = _shardings(params)[0]
    assert any(not s.is_fully_replicated for s in jax.tree.leaves(p_sh))
    adam = next(s for s in opt_state if hasattr(s, "mu"))
    assert _shardings(adam.mu)[0] == p_sh and _shardings(adam.nu)[0] == p_sh
    assert adam.count.sharding.is_fully_replicated
    assert len(adam.count.sharding.device_set) == len(
        jax.tree.leaves(p_sh)[0].device_set)
    assert parallel.state_shardings(*abstract, p_sh) == _shardings(
        params, opt_state)

"""The sharded train step (``gpt2.build_train_step`` on a state placed by
``shard_train_state``) against the plain reference and against itself on one
device, on the suite's virtual CPU devices.

The size is small and keeps what makes GPT-2 XL awkward on ``fsdp=4``: 5
heads (they do not divide the axis, so a partitioner that reads the weights'
split as tensor parallelism reshuffles), an odd vocabulary (the embedding
cannot be split by rows), ``loss_chunks`` and ``remat`` on. Widths are
large enough that ``shard_params_fsdp`` splits the kernels (its
``min_size``) and leaves the attention output projection, biases and
layer norms replicated: both kinds of gradient reduction are in the step.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perfbench import reference
from ray_tpu import parallel
from ray_tpu.models import gpt2
from ray_tpu.parallel import mesh_utils

SIZES = {"vocab_size": 515, "n_positions": 64, "n_embd": 160, "n_layer": 2,
         "n_head": 5, "layer_norm_epsilon": 1e-6}
BATCH, SEQ, B1 = 8, 64, 0.9
MESHES = {"fsdp4": {"fsdp": 4}, "data2_fsdp2": {"data": 2, "fsdp": 2}}


def _config(dtype):
    return gpt2.GPT2Config(
        vocab_size=SIZES["vocab_size"], n_positions=SIZES["n_positions"],
        n_embd=SIZES["n_embd"], n_layer=SIZES["n_layer"],
        n_head=SIZES["n_head"], dtype=dtype, remat=True, loss_chunks=4)


def _mesh(axes):
    n = int(np.prod(list(axes.values())))
    return parallel.create_mesh(axes, devices=jax.devices()[:n])


def _state(config, seed=0):
    model, params, tx, opt_state = gpt2.make_train_state(
        config, jax.random.PRNGKey(seed))
    return model, tx, params, opt_state


def _tokens(seed=1):
    rng = np.random.default_rng(seed)
    return rng.integers(0, SIZES["vocab_size"], (BATCH, SEQ + 1),
                        dtype=np.int32)


def _batch(tokens, place=jnp.asarray):
    return {"input_ids": place(tokens[:, :-1]), "labels": place(tokens[:, 1:])}


def _grads_from_mu(opt_state):
    """The gradient as the benchmark reads it: after the first AdamW step
    from zero moments, mu = (1 - b1) * g."""
    mu = next(s.mu for s in opt_state if hasattr(s, "mu"))
    return jax.tree.map(lambda m: np.asarray(m, np.float32) / (1.0 - B1), mu)


def _rel_error(a, b):
    """|a - b| / |b| over whole trees."""
    pairs = list(zip(jax.tree.leaves(a), jax.tree.leaves(b)))
    diff = np.sqrt(sum(float(np.sum((x - y) ** 2)) for x, y in pairs))
    return diff / np.sqrt(sum(float(np.sum(y ** 2)) for _, y in pairs))


def _sharded_first_step(config, axes, tokens):
    mesh = _mesh(axes)
    model, tx, params, opt_state = _state(config)
    params, opt_state = gpt2.shard_train_state(params, opt_state, mesh,
                                               fsdp=True)
    batch = _batch(tokens, lambda a: jax.device_put(
        a, mesh_utils.data_sharding(mesh)))
    step = gpt2.build_train_step(model, tx, donate=False)
    return step, (params, opt_state, batch), step(params, opt_state, batch)


@pytest.mark.parametrize("axes", MESHES.values(), ids=MESHES.keys())
def test_sharded_step_agrees_with_the_plain_reference(axes):
    """float32 compute, so that nothing but the order of float32 sums may
    differ between the sharded program and the reference: the loss within
    1e-6 and the gradient within 1e-5 of the reference's in norm of the
    difference (read: 1.4e-7 and 7e-7). A gradient reduced in bfloat16
    (2**-9 an element: 2e-3), a sum where the mean over the axis belongs
    (a factor 4) or a shard left out of a gather all fail by orders."""
    tokens = _tokens()
    config = _config(jnp.float32)
    _, _, (_, opt_state, loss) = _sharded_first_step(config, axes, tokens)
    _, _, params, _ = _state(config)
    ref_loss, ref_grads = reference.make_loss_and_grad(SIZES)(
        params, *_batch(tokens).values())
    assert abs(float(loss) - float(ref_loss)) <= 1e-6 * abs(float(ref_loss))
    ref_grads = jax.tree.map(np.asarray, ref_grads)
    assert _rel_error(_grads_from_mu(opt_state), ref_grads) <= 1e-5


@pytest.mark.parametrize("dtype,loss_tol,grad_tol,weights_tol", [
    # float32: the same sums in another order (read: 0, 4e-7, 3.5e-6)
    (jnp.float32, 1e-6, 1e-5, 1e-5),
    # bfloat16 compute, what the cells run: on one device a weight's
    # gradient is summed over the whole batch in float32 and rounded to
    # bfloat16 once; on four each chip rounds its part and the parts are
    # added in bfloat16, 2**-9 an element each time (read: 7e-6, 1.0e-2).
    # AdamW's first step moves a weight by the learning rate times its
    # gradient's sign, so the updated weights differ where a gradient near
    # zero changed sign (read: 4.7e-4 of the weights' norm)
    (jnp.bfloat16, 1e-4, 3e-2, 2e-3),
], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("axes", MESHES.values(), ids=MESHES.keys())
def test_sharded_step_agrees_with_the_one_device_step(axes, dtype, loss_tol,
                                                      grad_tol, weights_tol):
    tokens = _tokens()
    config = _config(dtype)
    _, _, (params, opt_state, loss) = _sharded_first_step(config, axes,
                                                          tokens)
    model, tx, params1, opt_state1 = _state(config)
    params1, opt_state1, loss1 = gpt2.build_train_step(
        model, tx, donate=False)(params1, opt_state1, _batch(tokens))
    assert abs(float(loss) - float(loss1)) <= loss_tol * abs(float(loss1))
    assert _rel_error(_grads_from_mu(opt_state),
                      _grads_from_mu(opt_state1)) <= grad_tol
    as_np = lambda t: jax.tree.map(lambda x: np.asarray(x, np.float32), t)
    assert _rel_error(as_np(params), as_np(params1)) <= weights_tol


@pytest.mark.parametrize("axes", MESHES.values(), ids=MESHES.keys())
def test_sharded_step_compiles_once_and_keeps_its_shardings(axes):
    """The state comes back in the shardings it went in, so the second and
    third steps find the first one's program: one jit for the layout, one
    entry in its cache."""
    step, (params, opt_state, batch), _ = _sharded_first_step(
        _config(jnp.bfloat16), axes, _tokens())
    shardings = lambda *trees: jax.tree.map(lambda x: x.sharding, trees)
    before = shardings(params, opt_state)
    assert any(not s.is_fully_replicated for s in jax.tree.leaves(before))
    for _ in range(3):
        params, opt_state, _ = step(params, opt_state, batch)
        assert shardings(params, opt_state) == before
    (_, jitted), = step._by_layout
    assert jitted._cache_size() == 1


def test_replicated_state_on_a_mesh_keeps_its_shardings():
    """Plain data parallelism (``shard_train_state`` without ``fsdp``): the
    same step, the state replicated in and out."""
    mesh = _mesh({"data": 4})
    model, tx, params, opt_state = _state(_config(jnp.float32))
    params, opt_state = gpt2.shard_train_state(params, opt_state, mesh)
    batch = gpt2.shard_batch(_batch(_tokens()), mesh)
    step = gpt2.build_train_step(model, tx, donate=False)
    new_params, new_opt_state, _ = step(params, opt_state, batch)
    for old, new in zip(jax.tree.leaves((params, opt_state)),
                        jax.tree.leaves((new_params, new_opt_state))):
        assert new.sharding == old.sharding and new.sharding.is_fully_replicated


def _parent_step(model, tx):
    """``build_train_step`` as it was before the step read its layout."""
    import optax

    def step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(gpt2.loss_fn)(params, model, batch)
        updates, opt_state = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), opt_state, loss

    return jax.jit(step, donate_argnums=(0, 1))


@pytest.mark.parametrize("loss_chunks", [0, 4], ids=["fused", "chunked"])
def test_without_a_mesh_the_step_lowers_to_the_program_it_was(monkeypatch,
                                                              loss_chunks):
    """On one device nothing of the sharded step is in the program: no
    sharding constraint, and the text equals that of the step as it was,
    lowered with ``on_batch_axes`` taken out of the model."""
    import dataclasses

    config = dataclasses.replace(_config(jnp.bfloat16),
                                 loss_chunks=loss_chunks)
    model, tx, params, opt_state = _state(config)
    args = (params, opt_state, _batch(_tokens()))
    text = gpt2.build_train_step(model, tx, donate=True).lower(*args).as_text()
    assert "sharding" not in text.lower()
    monkeypatch.setattr(gpt2, "on_batch_axes", lambda x, batch_dim=0: x)
    assert text == _parent_step(model, tx).lower(*args).as_text()


def test_embedding_lookup_is_flax_embeds_own():
    """``GPT2.__call__`` looks its token embedding up itself, on a table
    gathered whole; without a mesh that is ``nn.Embed.__call__``'s
    program."""
    import flax.linen as nn

    embed = nn.Embed(SIZES["vocab_size"], SIZES["n_embd"], dtype=jnp.bfloat16)
    ids = jnp.asarray(_tokens()[:, :-1])
    variables = embed.init(jax.random.PRNGKey(0), ids)

    def ours(variables, ids):
        table = variables["params"]["embedding"].astype(jnp.bfloat16)
        return jnp.take(mesh_utils.on_batch_axes(table, batch_dim=None), ids,
                        axis=0)

    lower = lambda f: jax.jit(f).lower(variables, ids).as_text()
    assert lower(ours).replace("ours", "apply") == lower(embed.apply).replace(
        "ours", "apply")

"""Pipeline parallelism: ppermute pipeline vs sequential reference
(forward AND gradients), and the GPT-2 pipelined train step.
(SURVEY §2.9: PP is first-class for the TPU build; reference exercises it
only via external Alpa release tests.)"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec

from ray_tpu import parallel
from ray_tpu.parallel.pipeline import (
    build_pipeline_fn,
    pipeline_apply,
    stack_stage_params,
)

S = 4  # stages


def _mesh():
    return parallel.create_mesh({"pipeline": S})


def _stage_params(key, d=16):
    ks = jax.random.split(key, S)
    per_stage = [
        {"w": jax.random.normal(k, (d, d)) / np.sqrt(d),
         "b": jax.random.normal(k, (d,)) * 0.1}
        for k in ks
    ]
    return stack_stage_params(per_stage), per_stage


def _stage_fn(p, x):
    return jnp.tanh(x @ p["w"] + p["b"])


def test_pipeline_forward_matches_sequential():
    mesh = _mesh()
    stacked, per_stage = _stage_params(jax.random.PRNGKey(0))
    mb = jax.random.normal(jax.random.PRNGKey(1), (6, 8, 16))  # M=6

    fn = build_pipeline_fn(_stage_fn, mesh)
    got = fn(stacked, mb)

    want = mb
    for p in per_stage:
        want = _stage_fn(p, want)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)


def test_pipeline_grads_match_sequential():
    """Reverse-mode through the ppermute schedule must equal sequential
    autodiff — stage grads route back through the reverse rotation."""
    mesh = _mesh()
    stacked, per_stage = _stage_params(jax.random.PRNGKey(2))
    mb = jax.random.normal(jax.random.PRNGKey(3), (4, 8, 16))
    w = jax.random.normal(jax.random.PRNGKey(4), mb.shape)

    from jax import shard_map

    def pp_loss(stacked, mb):
        def local(stacked, mb):
            own = jax.tree.map(lambda p: p[0], stacked)
            return pipeline_apply(_stage_fn, own, mb, axis_name="pipeline")

        y = shard_map(
            local, mesh=mesh,
            in_specs=(PartitionSpec("pipeline"), PartitionSpec()),
            out_specs=PartitionSpec(),
        )(stacked, mb)
        return (y * w).sum()

    def seq_loss(stacked, mb):
        y = mb
        for s in range(S):
            y = _stage_fn(jax.tree.map(lambda p: p[s], stacked), y)
        return (y * w).sum()

    g_pp = jax.grad(pp_loss)(stacked, mb)
    g_seq = jax.grad(seq_loss)(stacked, mb)
    for a, b in zip(jax.tree.leaves(g_pp), jax.tree.leaves(g_seq)):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)


def test_gpt2_pipeline_train_step():
    """PP loss at init matches the plain (non-parallel) model — same
    blocks, same init — and a few pipelined steps reduce it."""
    from ray_tpu.models import gpt2

    mesh = parallel.create_mesh({"data": 2, "pipeline": S})
    config = gpt2.GPT2Config.small_test(n_layer=4)  # 1 block per stage

    model, ref_params, _, _ = gpt2.make_train_state(config, jax.random.PRNGKey(0))
    pp_params, tx, opt_state = gpt2.make_pipeline_train_state(
        config, jax.random.PRNGKey(0), n_stages=S
    )
    pp_params, opt_state = gpt2.shard_pipeline_state(pp_params, opt_state, mesh)
    step = gpt2.build_train_step_pp(config, tx, mesh, n_microbatches=2,
                                    donate=False)

    batch = gpt2.synthetic_batch(jax.random.PRNGKey(1), 4, 32,
                                 config.vocab_size)
    ref_loss = float(gpt2.loss_fn(ref_params, model, batch))

    p, o = pp_params, opt_state
    losses = []
    for _ in range(4):
        p, o, loss = step(p, o, batch)
        losses.append(float(loss))
    assert abs(losses[0] - ref_loss) < 0.05, (losses[0], ref_loss)
    assert losses[-1] < losses[0], losses


def test_gpt2_pipeline_masked_batch():
    """The PP step accepts a padded batch with a mask and matches the
    plain model's masked loss (the batch spec is a pytree prefix)."""
    from ray_tpu.models import gpt2

    mesh = parallel.create_mesh({"data": 2, "pipeline": S})
    config = gpt2.GPT2Config.small_test(n_layer=4)

    model, ref_params, _, _ = gpt2.make_train_state(config, jax.random.PRNGKey(0))
    pp_params, tx, opt_state = gpt2.make_pipeline_train_state(
        config, jax.random.PRNGKey(0), n_stages=S
    )
    pp_params, opt_state = gpt2.shard_pipeline_state(pp_params, opt_state, mesh)
    step = gpt2.build_train_step_pp(config, tx, mesh, n_microbatches=2,
                                    donate=False)
    batch = gpt2.synthetic_batch(jax.random.PRNGKey(5), 4, 32,
                                 config.vocab_size)
    # mask counts DIFFER across data shards (rows 0-1 vs 2-3): the PP loss
    # must be the global token-weighted mean, not a mean of per-shard
    # masked means (which would up-weight the sparser shard)
    mask = np.ones((4, 32), np.float32)
    mask[:2, 8:] = 0.0   # shard 0: 8 valid tokens per row
    mask[2:, 24:] = 0.0  # shard 1: 24 valid tokens per row
    batch["mask"] = jnp.asarray(mask)
    ref_loss = float(gpt2.loss_fn(ref_params, model, batch))
    _, _, loss = step(pp_params, opt_state, batch)
    assert abs(float(loss) - ref_loss) < 0.05, (float(loss), ref_loss)

"""LearnerGroup (multi-learner DDP) tests.

Analog of ray: rllib/core/learner/tests/test_learner_group.py — N learner
actors shard the batch, gradients mean-allreduce in lockstep, replicas
stay bit-identical, and multi-learner training matches single-learner
learning on CartPole.
"""

import numpy as np
import pytest

import ray_tpu
from ray_tpu.rllib import IMPALAConfig, PPOConfig


@pytest.mark.slow
def test_replicas_stay_in_sync(ray_start_regular):
    """After updates, every learner replica holds identical params (they
    all applied the same averaged gradients from the same init)."""
    algo = (
        PPOConfig()
        .environment("CartPole-native")
        .env_runners(num_env_runners=2, rollout_fragment_length=128)
        .learners(num_learners=2)
        .training(lr=5e-3, num_epochs=2, minibatch_size=64)
        .debugging(seed=0)
        .build()
    )
    algo.train()
    algo.train()
    from ray_tpu.rllib.learner_group import LearnerGroup

    assert isinstance(algo.learner, LearnerGroup)
    w0, w1 = ray_tpu.get(
        [w.get_weights.remote() for w in algo.learner.workers], timeout=60
    )
    import jax

    leaves0 = jax.tree.leaves(w0)
    leaves1 = jax.tree.leaves(w1)
    assert len(leaves0) == len(leaves1) and leaves0
    for a, b in zip(leaves0, leaves1):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    algo.stop()


@pytest.mark.slow
def test_ppo_two_learners_matches_single(ray_start_regular):
    """CartPole learning with 2 DDP learners reaches the single-learner
    bar (the acceptance: multi-learner matches 1-learner)."""
    algo = (
        PPOConfig()
        .environment("CartPole-native")
        .env_runners(num_env_runners=2, rollout_fragment_length=256)
        .learners(num_learners=2)
        .training(lr=5e-3, num_epochs=6, minibatch_size=128)
        .debugging(seed=0)
        .build()
    )
    best = 0.0
    for _ in range(25):
        result = algo.train()
        best = max(best, result.get("episode_return_mean", 0.0))
        if best >= 120:
            break
    algo.stop()
    assert best >= 100, f"2-learner PPO failed to learn CartPole (best={best})"


@pytest.mark.slow
def test_impala_two_learners_improves(ray_start_regular):
    algo = (
        IMPALAConfig()
        .environment("CartPole-native")
        .env_runners(num_env_runners=2, rollout_fragment_length=256)
        .learners(num_learners=2)
        .debugging(seed=0)
        .build()
    )
    first, best = None, 0.0
    for _ in range(30):
        result = algo.train()
        r = result.get("episode_return_mean")
        if r is not None:
            first = first if first is not None else r
            best = max(best, r)
    algo.stop()
    assert best > first + 10, (first, best)


@pytest.mark.slow
def test_checkpoint_roundtrip_with_group(ray_start_regular):
    """save/load must round-trip through the group (weights + opt state
    fan out to every replica)."""
    algo = (
        PPOConfig()
        .environment("CartPole-native")
        .env_runners(num_env_runners=1, rollout_fragment_length=128)
        .learners(num_learners=2)
        .training(num_epochs=2, minibatch_size=64)
        .debugging(seed=0)
        .build()
    )
    algo.train()
    ckpt = algo.save_checkpoint()
    w_before = algo.learner.get_weights()
    algo.train()  # drift past the checkpoint
    algo.load_checkpoint(ckpt)
    w_after = algo.learner.get_weights()
    import jax

    for a, b in zip(jax.tree.leaves(w_before), jax.tree.leaves(w_after)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)
    algo.stop()

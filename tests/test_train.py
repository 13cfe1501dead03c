"""Train tests (analog of ray: python/ray/train/tests/test_data_parallel_trainer.py)."""

import os

import numpy as np
import pytest

import ray_tpu


# Workers must run JAX on CPU (tests never grab the TPU chip).
_CPU_ENV = {
    "JAX_PLATFORMS": "cpu",
    "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
}


def test_worker_group_basic(ray_start_regular):
    from ray_tpu.train import WorkerGroup

    wg = WorkerGroup(2, {"CPU": 1})
    outs = wg.execute(lambda: os.getpid())
    assert len(outs) == 2 and outs[0] != outs[1]
    wg.shutdown()


def test_data_parallel_trainer_reports(ray_start_regular):
    from ray_tpu import train

    def loop(config):
        ctx = train.get_context()
        for step in range(3):
            train.report({
                "step": step,
                "rank": ctx.get_world_rank(),
                "world_size": ctx.get_world_size(),
                "loss": 1.0 / (step + 1),
            })

    trainer = train.DataParallelTrainer(
        loop,
        scaling_config=train.ScalingConfig(num_workers=2),
        run_config=train.RunConfig(name="t_basic", storage_path="/tmp/rt_test_results"),
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["step"] == 2
    assert result.metrics["world_size"] == 2
    assert result.metrics["rank"] == 0


def test_trainer_checkpointing(ray_start_regular):
    from ray_tpu import train
    from ray_tpu.air import Checkpoint

    def loop(config):
        start = 0
        ck = train.get_checkpoint()
        if ck is not None:
            start = ck.to_dict()["step"] + 1
        for step in range(start, start + 2):
            train.report(
                {"step": step},
                checkpoint=Checkpoint.from_dict({"step": step}),
            )

    trainer = train.DataParallelTrainer(
        loop,
        scaling_config=train.ScalingConfig(num_workers=1),
        run_config=train.RunConfig(name="t_ckpt", storage_path="/tmp/rt_test_results"),
    )
    result = trainer.fit()
    assert result.metrics["step"] == 1
    assert result.checkpoint is not None
    # resume
    trainer2 = train.DataParallelTrainer(
        loop,
        scaling_config=train.ScalingConfig(num_workers=1),
        run_config=train.RunConfig(name="t_ckpt2", storage_path="/tmp/rt_test_results"),
        resume_from_checkpoint=result.checkpoint,
    )
    result2 = trainer2.fit()
    assert result2.metrics["step"] == 3


def test_trainer_failure(ray_start_regular):
    from ray_tpu import train

    def loop(config):
        raise ValueError("train loop exploded")

    trainer = train.DataParallelTrainer(
        loop,
        scaling_config=train.ScalingConfig(num_workers=1),
        run_config=train.RunConfig(name="t_fail", storage_path="/tmp/rt_test_results"),
    )
    result = trainer.fit()
    assert result.error is not None
    assert "exploded" in str(result.error)


def test_jax_trainer_dp_sync(ray_start_regular):
    """Two JAX CPU workers train a tiny model data-parallel; gradients sync
    via the host collective; losses match across workers each step."""
    from ray_tpu import train

    def loop(config):
        import jax
        import jax.numpy as jnp
        import numpy as np

        from ray_tpu.util import collective as col

        ctx = train.get_context()
        rank = ctx.get_world_rank()
        world = ctx.get_world_size()

        # deterministic per-rank data shard
        rng = np.random.default_rng(42 + rank)
        X = rng.normal(size=(32, 4)).astype(np.float32)
        w_true = np.array([1.0, -2.0, 3.0, 0.5], dtype=np.float32)
        y = X @ w_true

        w = jnp.zeros((4,))

        @jax.jit
        def grad_fn(w, X, y):
            def loss(w):
                return jnp.mean((X @ w - y) ** 2)

            return jax.value_and_grad(loss)(w)

        if world > 1:
            col.init_collective_group(world, rank, backend="store",
                                      group_name="dp_test")
        for step in range(5):
            loss, g = grad_fn(w, X, y)
            g = np.asarray(g)
            if world > 1:
                g = col.allreduce(g, "dp_test", op=col.ReduceOp.MEAN)
            w = w - 0.1 * jnp.asarray(g)
            train.report({"step": step, "loss": float(loss), "rank": rank})

    trainer = train.JaxTrainer(
        loop,
        jax_config=train.JaxConfig(env_vars=_CPU_ENV),
        scaling_config=train.ScalingConfig(num_workers=2),
        run_config=train.RunConfig(name="t_jaxdp", storage_path="/tmp/rt_test_results"),
    )
    result = trainer.fit()
    assert result.error is None, result.error
    assert result.metrics["step"] == 4
    assert result.metrics["loss"] < 15.0


def test_jax_trainer_mesh_in_worker(ray_start_regular):
    """A worker builds a 4-device virtual mesh and runs a sharded train step
    (validates the in-graph psum path without TPU hardware)."""
    from ray_tpu import train

    def loop(config):
        import jax
        import jax.numpy as jnp

        from ray_tpu import parallel
        from ray_tpu.models import gpt2

        assert len(jax.devices()) == 4
        mesh = parallel.create_mesh({"data": 4})
        cfg = gpt2.GPT2Config.small_test()
        model, params, tx, opt_state = gpt2.make_train_state(
            cfg, jax.random.PRNGKey(0)
        )
        params, opt_state = gpt2.shard_train_state(params, opt_state, mesh)
        step_fn = gpt2.build_train_step(model, tx, donate=False)
        batch = gpt2.synthetic_batch(jax.random.PRNGKey(1), 8, 32, cfg.vocab_size)
        batch = gpt2.shard_batch(batch, mesh)
        losses = []
        for i in range(3):
            params, opt_state, loss = step_fn(params, opt_state, batch)
            losses.append(float(loss))
        train.report({"losses": losses})

    trainer = train.JaxTrainer(
        loop,
        jax_config=train.JaxConfig(env_vars=_CPU_ENV),
        scaling_config=train.ScalingConfig(num_workers=1),
        run_config=train.RunConfig(name="t_mesh", storage_path="/tmp/rt_test_results"),
    )
    result = trainer.fit()
    assert result.error is None, result.error
    losses = result.metrics["losses"]
    assert losses[2] < losses[0]  # it learns


def test_jax_trainer_latent_attention_expert_model(ray_start_regular):
    """The latent-attention, routed-expert model through the normal path:
    ``JaxTrainer.fit()`` runs a loop that builds the state, places it on a
    4-device mesh by the model's own rule, steps with the one step builder
    and reports each step's loss, its two terms and the experts' load."""
    from ray_tpu import train

    def loop(config):
        import jax
        import jax.numpy as jnp

        from ray_tpu import parallel
        from ray_tpu.models import gpt2, mla_moe

        mesh = parallel.create_mesh({"data": 4})
        cfg = mla_moe.MLAMoEConfig.small_test(dtype=jnp.float32,
                                              expert_shard=(0, 2), remat=True)
        model, params, tx, opt_state = mla_moe.make_train_state(
            cfg, jax.random.PRNGKey(0))
        params, opt_state = mla_moe.shard_train_state(params, opt_state, mesh)
        step_fn = mla_moe.build_train_step(model, tx, donate=False)
        batch = gpt2.shard_batch(gpt2.synthetic_batch(
            jax.random.PRNGKey(1), 8, 32, cfg.vocab_size), mesh)
        for _ in range(4):
            params, opt_state, *out = step_fn(params, opt_state, batch)
            train.report(mla_moe.step_metrics(
                *out, pairs=8 * 32 * cfg.num_experts_per_tok))

    trainer = train.JaxTrainer(
        loop,
        jax_config=train.JaxConfig(env_vars=_CPU_ENV),
        scaling_config=train.ScalingConfig(num_workers=1),
        run_config=train.RunConfig(name="t_mla_moe",
                                   storage_path="/tmp/rt_test_results"),
    )
    result = trainer.fit()
    assert result.error is None, result.error
    last = result.metrics
    assert last["loss"] == pytest.approx(
        last["loss_main"] + 0.3 * last["loss_mtp"], rel=1e-5)
    assert last["loss"] < 7.3          # ln(256) x 1.3 at the first step
    assert 0 < last["expert_tokens_mean"] <= last["expert_tokens_max"] <= 256
    assert 0 < last["rows_present"] <= last["rows_buffered"]


def test_torch_trainer_gloo(ray_start_regular):
    """ray parity: TorchTrainer with a real torch.distributed gloo group."""
    from ray_tpu import train

    def loop(config):
        import torch
        import torch.distributed as dist

        rank = dist.get_rank()
        world = dist.get_world_size()
        t = torch.ones(4) * (rank + 1)
        dist.all_reduce(t, op=dist.ReduceOp.SUM)
        train.report({"sum": t.tolist(), "world": world})

    trainer = train.TorchTrainer(
        loop,
        scaling_config=train.ScalingConfig(num_workers=2),
        run_config=train.RunConfig(name="t_torch", storage_path="/tmp/rt_test_results"),
    )
    result = trainer.fit()
    assert result.error is None, result.error
    assert result.metrics["sum"] == [3.0, 3.0, 3.0, 3.0]
    assert result.metrics["world"] == 2


def test_tensorflow_trainer_tf_config_and_fit(ray_start_regular):
    """TF_CONFIG is wired per worker (cluster spec + task index); a
    single-worker keras fit runs under MultiWorkerMirroredStrategy
    (ray parity: tensorflow_trainer.py)."""
    import json as _json

    from ray_tpu import train
    from ray_tpu.train import TensorflowTrainer

    def probe_loop():
        import os

        from ray_tpu import train as train_mod

        cfg = _json.loads(os.environ["TF_CONFIG"])
        ctx = train_mod.get_context()
        train_mod.report({
            "task_index": cfg["task"]["index"],
            "world": len(cfg["cluster"]["worker"]),
            "rank": ctx.get_world_rank(),
        })

    trainer = TensorflowTrainer(
        probe_loop, scaling_config=train.ScalingConfig(num_workers=2)
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["world"] == 2
    assert result.metrics["task_index"] == result.metrics["rank"]

    def keras_loop():
        import numpy as np
        import tensorflow as tf

        from ray_tpu import train as train_mod

        strategy = tf.distribute.MultiWorkerMirroredStrategy()
        with strategy.scope():
            model = tf.keras.Sequential([
                tf.keras.layers.Dense(4, activation="relu",
                                      input_shape=(2,)),
                tf.keras.layers.Dense(1),
            ])
            model.compile(optimizer="sgd", loss="mse")
        X = np.random.rand(64, 2).astype("float32")
        y = (X.sum(axis=1, keepdims=True)).astype("float32")
        hist = model.fit(X, y, epochs=2, verbose=0)
        train_mod.report({"loss": float(hist.history["loss"][-1])})

    trainer = TensorflowTrainer(
        keras_loop, scaling_config=train.ScalingConfig(num_workers=1)
    )
    result = trainer.fit()
    assert result.error is None
    assert result.metrics["loss"] >= 0.0


def test_sklearn_trainer(ray_start_regular):
    import numpy as np
    import pandas as pd

    from ray_tpu import data as rdata
    from ray_tpu.train import SklearnTrainer
    from sklearn.linear_model import LogisticRegression

    rng = np.random.default_rng(0)
    X = rng.normal(size=(200, 3))
    y = (X[:, 0] + X[:, 1] > 0).astype(int)
    df = pd.DataFrame({"a": X[:, 0], "b": X[:, 1], "c": X[:, 2], "label": y})
    trainer = SklearnTrainer(
        estimator=LogisticRegression(max_iter=200),
        datasets={"train": rdata.from_pandas(df)},
        label_column="label",
    )
    result = trainer.fit()
    assert result.error is None, result.error
    assert result.metrics["train_score"] > 0.9
    import cloudpickle

    model = cloudpickle.loads(result.checkpoint.to_dict()["model"])
    assert model.predict(np.array([[2.0, 2.0, 0.0]]))[0] == 1


@pytest.mark.slow
def test_torch_trainer_ddp_convergence(ray_start_regular):
    """Convergence (not just collectives): a 2-worker DDP regression run
    must actually minimize the loss, with gradient averaging across the
    gloo group keeping replicas identical (ray parity: the torch
    benchmark workloads assert learning, release/air_tests)."""
    from ray_tpu import train

    def loop(config):
        import torch
        import torch.distributed as dist
        from torch.nn.parallel import DistributedDataParallel as DDP

        torch.manual_seed(0)
        rank = dist.get_rank()
        # y = 3x - 1 with per-worker data shards
        g = torch.Generator().manual_seed(100 + rank)
        x = torch.rand(256, 1, generator=g) * 4 - 2
        y = 3.0 * x - 1.0

        model = DDP(torch.nn.Linear(1, 1))
        opt = torch.optim.SGD(model.parameters(), lr=0.1)
        first = last = None
        for _ in range(60):
            opt.zero_grad()
            loss = torch.nn.functional.mse_loss(model(x), y)
            loss.backward()  # DDP averages grads across the group
            opt.step()
            first = first if first is not None else loss.item()
            last = loss.item()
        w = model.module.weight.item()
        b = model.module.bias.item()
        train.report({"first": first, "last": last, "w": w, "b": b})

    trainer = train.TorchTrainer(
        loop,
        scaling_config=train.ScalingConfig(num_workers=2),
        run_config=train.RunConfig(name="t_torch_conv",
                                   storage_path="/tmp/rt_test_results"),
    )
    result = trainer.fit()
    assert result.error is None, result.error
    m = result.metrics
    assert m["last"] < m["first"] * 0.05, m  # loss actually minimized
    assert abs(m["w"] - 3.0) < 0.2 and abs(m["b"] + 1.0) < 0.2, m

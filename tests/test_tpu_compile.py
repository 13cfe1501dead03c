"""The main path's kernels and train steps, compiled for a described v5e.

No chip is attached: the TPU compiler installed here compiles for a
topology that is only described (on-chip-measurement guide, section 2).
What it refuses — a Mosaic kernel it cannot lower, a sharded program it
cannot partition, a step that does not fit HBM — it would refuse on the
chip too, so these run before every chip call at no chip time. Nothing
executes: a pass here says nothing about results or speed.

All tests live in this one file and describe the topology inside a
module-scoped fixture: only one process may load the TPU library, and it
must be the xdist worker that was handed this file.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import (Mesh, NamedSharding, PartitionSpec,
                          SingleDeviceSharding)

from ray_tpu.models import gpt2
from ray_tpu.ops.attention import flash_attention

HBM_BYTES = 16 * 1024**3  # one v5e chip
BATCH, SEQ = 16, 1024


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def no_compile_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without a chip: keep the cache out of it."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    old = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", old)
    cc.reset_cache()


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer code that asks ``jax.default_backend()`` (the flash
    dispatcher) onto its TPU branch: the process itself sees the CPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    yield
    jax.clear_caches()


def _with_sharding(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _train_step_args(attention, state_sharding, batch_sharding, batch=BATCH):
    """(jitted step, abstract args) for the full-width GPT-2-124M step."""
    config = gpt2.GPT2Config.gpt2_124m(loss_chunks=8, attention=attention)
    model = gpt2.GPT2(config)
    tx = gpt2.make_optimizer()

    def state(rng):
        _, params, _, opt_state = gpt2.make_train_state(config, rng)
        return params, opt_state

    params, opt_state = _with_sharding(
        jax.eval_shape(state, jax.random.PRNGKey(0)), state_sharding)
    ids = jax.ShapeDtypeStruct((batch, SEQ), jnp.int32,
                               sharding=batch_sharding)
    step = gpt2.build_train_step(model, tx, donate=True)
    return step, (params, opt_state, {"input_ids": ids, "labels": ids})


def _device_bytes(compiled):
    m = compiled.memory_analysis()
    return (m.temp_size_in_bytes + m.argument_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "fwd_bwd"])
def test_flash_kernel_compiles(topo, no_compile_cache, backward):
    """(batch 16 x 12 heads, 1024, 64) bf16 — the shape the model calls."""
    x = jax.ShapeDtypeStruct((16 * 12, SEQ, 64), jnp.bfloat16,
                             sharding=SingleDeviceSharding(topo.devices[0]))

    def fwd(q, k, v):
        return flash_attention(q, k, v, causal=True, impl="pallas")

    def loss(q, k, v):
        return fwd(q, k, v).astype(jnp.float32).sum()

    fn = jax.grad(loss, argnums=(0, 1, 2)) if backward else fwd
    text = jax.jit(fn).lower(x, x, x).compile().as_text()
    assert "tpu_custom_call" in text


def test_train_step_auto_fits_one_chip(topo, no_compile_cache):
    one = SingleDeviceSharding(topo.devices[0])
    step, args = _train_step_args("auto", one, one)
    compiled = step.lower(*args).compile()
    assert _device_bytes(compiled) < HBM_BYTES


def test_train_step_flash_fits_one_chip(topo, no_compile_cache, on_tpu):
    one = SingleDeviceSharding(topo.devices[0])
    step, args = _train_step_args("flash", one, one)
    compiled = step.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("attention", ["auto", "flash"])
def test_train_step_data_parallel_4_chips(topo, no_compile_cache, on_tpu,
                                          attention):
    """Global batch 64 over data=4, state replicated: the partitioner must
    add the gradient all-reduce, and — for flash — must be handed the
    Mosaic kernel already split per batch shard."""
    mesh = Mesh(np.array(topo.devices).reshape(4), ("data",))
    step, args = _train_step_args(
        attention, NamedSharding(mesh, PartitionSpec()),
        NamedSharding(mesh, PartitionSpec("data")), batch=4 * BATCH)
    compiled = step.lower(*args).compile()
    text = compiled.as_text()
    assert "all-reduce" in text
    assert ("tpu_custom_call" in text) == (attention == "flash")
    assert _device_bytes(compiled) < HBM_BYTES

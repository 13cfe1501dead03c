"""The gated, grouped RMSNorm as one function (``ops/norm.py``): the ``jnp``
twin against a hand computation, the kernel pair (interpreted) against the
twin and its ``vjp``, the one rounding, what the shapes' rule refuses, the
mesh's two questions, and the counter."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ray_tpu._private import steptrace
from ray_tpu.ops import norm

_F32, _BF16, EPS = jnp.float32, jnp.bfloat16, 1e-5


def _operands(shape, dtype=_F32, seed=0):
    """(y, z, scale, the result's cotangent)."""
    keys = jax.random.split(jax.random.PRNGKey(seed), 4)
    y = 2.0 * jax.random.normal(keys[0], shape, _F32)
    z = jax.random.normal(keys[1], shape, _F32)
    scale = 1 + 0.1 * jax.random.normal(keys[2], shape[-1:], _F32)
    do = jax.random.normal(keys[3], shape, _F32)
    return y.astype(dtype), z.astype(dtype), scale, do.astype(dtype)


def _by_hand(y, z, scale, groups, eps=EPS):
    y, z = np.asarray(y, np.float64), np.asarray(z, np.float64)
    gated = (y * z / (1 + np.exp(-z))).reshape(*y.shape[:-1], groups, -1)
    normed = gated / np.sqrt((gated ** 2).mean(-1, keepdims=True) + eps)
    return normed.reshape(y.shape) * np.asarray(scale, np.float64)


@pytest.mark.parametrize("groups", [1, 2, 8])
def test_the_twin_is_the_gate_then_the_groups_norm(groups):
    """``y * silu(z)`` first, then ``/ rms`` over each group, one weight a
    channel; a group's scale does not move another's."""
    y, z, scale, _ = _operands((2, 6, 64))
    got = norm.gated_group_rms_norm_jnp(y, z, scale, groups=groups, eps=EPS)
    np.testing.assert_allclose(got, _by_hand(y, z, scale, groups),
                               rtol=1e-5, atol=1e-6)
    run = 64 // groups
    louder = y.at[..., :run].multiply(100.0)
    np.testing.assert_allclose(
        norm.gated_group_rms_norm_jnp(louder, z, scale, groups=groups,
                                      eps=EPS)[..., run:],
        got[..., run:], rtol=1e-5, atol=1e-6)
    # any leading axes, as ``GroupRMSNorm`` took them
    np.testing.assert_array_equal(
        norm.gated_group_rms_norm(y[0, 0], z[0, 0], scale, groups=groups,
                                  eps=EPS), got[0, 0])


_KERNEL_SHAPES = [((2, 256, 1024), 2), ((1, 128, 4096), 8)]


@pytest.mark.parametrize("dtype", [_BF16, _F32], ids=["bf16", "f32"])
@pytest.mark.parametrize("shape,groups", _KERNEL_SHAPES,
                         ids=["2x256x1024g2", "1x128x4096g8"])
def test_the_kernels_are_the_twin(shape, groups, dtype):
    """Interpreted, values and all three gradients (dy, dz, dscale)."""
    y, z, scale, do = _operands(shape, dtype)
    assert norm.fits(y, groups)
    run = lambda impl: jax.vjp(functools.partial(
        norm.gated_group_rms_norm, groups=groups, eps=EPS, impl=impl),
        y, z, scale)
    (want, want_vjp), (got, got_vjp) = run("jnp"), run("pallas_interpret")
    assert got.dtype == want.dtype == dtype
    # the sums over a group are made in another order: a float32 margin,
    # which one rounding to bfloat16 may carry over a step
    step = 2.0 ** -7 if dtype == _BF16 else 1e-6
    np.testing.assert_allclose(got.astype(_F32), want.astype(_F32),
                               rtol=step, atol=1e-6)
    for a, b in zip(got_vjp(do), want_vjp(do)):
        assert a.dtype == b.dtype
        # the scale's gradient is a sum over every row, in another order
        np.testing.assert_allclose(a.astype(_F32), b.astype(_F32),
                                   rtol=20 * step, atol=20 * step)


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
def test_the_result_is_rounded_once(impl):
    """On inputs bfloat16 holds exactly, a float32 run rounded equals the
    bfloat16 run's result: nothing is rounded between the gate, the
    statistic and the scale."""
    y, z, scale, _ = _operands((2, 256, 1024), _BF16)
    fn = functools.partial(norm.gated_group_rms_norm, groups=2, eps=EPS,
                           impl=impl)
    np.testing.assert_array_equal(
        fn(y, z, scale).astype(_F32),
        fn(y.astype(_F32), z.astype(_F32), scale).astype(_BF16).astype(_F32))


def _said(fn, *shapes):
    """The ``norm/gated_group`` records of tracing ``fn``."""
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        jax.eval_shape(fn, *shapes)
        return [r["values"] for r in steptrace.snapshot()
                if r["kind"] == "counters"
                and r["name"] == "norm/gated_group"]
    finally:
        steptrace.set_enabled(False)


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    yield
    jax.clear_caches()


def _shapes(batch, seq, width, dtype=_BF16, sharding=None):
    x = jax.ShapeDtypeStruct((batch, seq, width), dtype, sharding=sharding)
    return x, x, jax.ShapeDtypeStruct((width,), _F32)


@pytest.mark.parametrize("seq,width,groups,why", [
    (256, 512, 8, "a group of 64 lanes, half a tile"),
    (200, 1024, 2, "a length no block of whole passes divides"),
    (256, 1000, 2, "a width its groups' tiles do not fill"),
    (256, 1024, 3, "groups that do not divide the width"),
    (256, 8192, 1, "a group wider than a chip has read"),
])
def test_what_the_shapes_rule_refuses_runs_the_twin(on_tpu, seq, width,
                                                    groups, why):
    """On a TPU too, and the record says ``kernel`` 0."""
    shapes = _shapes(2, seq, width)
    assert not norm.fits(shapes[0], groups), why
    assert norm.auto_impl(shapes[0], groups) == "jnp"
    if width % groups:
        return
    assert _said(functools.partial(norm.gated_group_rms_norm, groups=groups,
                                   eps=EPS), *shapes) == [
        {"tokens": 2 * seq, "width": width, "groups": groups,
         "bytes_needed": 2 * seq * width * 2 * 3, "backward": 0,
         "kernel": 0}]


def test_off_a_tpu_the_twin_runs_and_the_record_says_so():
    shapes = _shapes(2, 8192, 4096)
    assert norm.fits(shapes[0], 8)
    assert norm.auto_impl(shapes[0], 8) == "jnp"
    assert _said(functools.partial(norm.gated_group_rms_norm, groups=8,
                                   eps=EPS), *shapes) == [
        {"tokens": 16384, "width": 4096, "groups": 8,
         "bytes_needed": 16384 * 4096 * 2 * 3, "backward": 0, "kernel": 0}]


def test_on_a_tpu_each_traced_pass_of_the_kernels_says_so(on_tpu):
    """Forward and backward of the cell's shape: a record each, the
    backward's bytes five arrays'."""
    shapes = _shapes(2, 8192, 4096)
    assert norm.auto_impl(shapes[0], 8) == "pallas"
    step = lambda y, z, scale: jax.grad(lambda *a: jnp.sum(
        norm.gated_group_rms_norm(*a, groups=8, eps=EPS).astype(_F32)),
        argnums=(0, 1, 2))(y, z, scale)
    said = _said(step, *shapes)
    assert said == [
        {"tokens": 16384, "width": 4096, "groups": 8,
         "bytes_needed": 16384 * 4096 * 2 * (5 if backward else 3),
         "backward": backward, "kernel": 1} for backward in (0, 1)]


def test_the_blocks_are_whole_passes_within_the_bytes():
    """The cell's shape: 256 tokens a grid step forward and 128 backward
    (2 MiB and 1 MiB an operand's block); float32 halves them."""
    assert norm.block_tokens(8192, 4096, 2, False) == 256
    assert norm.block_tokens(8192, 4096, 2, True) == 128
    assert norm.block_tokens(8192, 4096, 4, True) == 64
    assert norm.block_tokens(96, 4096, 2, False) == 96
    assert norm.block_tokens(200, 1024, 2, False) == 0


@pytest.mark.parametrize("axes,shape,kernels", [
    (("data", "model"), (2, 2), False),
    (("data", "model"), (4, 1), True),
])
def test_auto_reads_the_mesh(on_tpu, axes, shape, kernels):
    """As ``tests/test_ops_mesh.py`` asks of the ops with an ``auto`` of
    their own: under any live axis but the batch's the twin."""
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(shape), axes)
    y, _, _ = _shapes(4, 256, 1024, sharding=NamedSharding(
        mesh, PartitionSpec("data")))
    seen = []
    jax.jit(lambda y: seen.append(norm.auto_impl(y, 2))).lower(y)
    assert seen == ["pallas" if kernels else "jnp"]


def test_under_a_batch_axis_the_kernels_run_a_batch_shard_each():
    """Traced under a mesh whose ``data`` axis splits the batch, the op in
    interpret mode is a ``shard_map`` over the rows, comes back split by
    rows and equals the unsharded call, the scale's gradient (summed over
    the shards) included."""
    y, z, scale, _ = _operands((2, 128, 1024), _BF16)
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    rows = NamedSharding(mesh, PartitionSpec("data"))
    operands = (y, z, scale)
    placed = (jax.device_put(y, rows), jax.device_put(z, rows), scale)

    def layer(*a):
        return norm.gated_group_rms_norm(*a, groups=2, eps=EPS,
                                         impl="pallas_interpret")

    fn = jax.jit(lambda *a: jax.value_and_grad(
        lambda *a: jnp.sum(jnp.sin(layer(*a).astype(_F32))),
        argnums=(0, 1, 2))(*a))
    alone, sharded = fn.trace(*operands), fn.trace(*placed)
    assert "shard_map" not in str(alone.jaxpr)
    assert "shard_map" in str(sharded.jaxpr)
    want = alone.lower().compile()(*operands)
    got = sharded.lower().compile()(*placed)
    assert got[1][0].sharding.spec[0] == "data"
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a.astype(_F32), b.astype(_F32),
                                   rtol=1e-5, atol=1e-4)

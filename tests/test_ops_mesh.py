"""The two questions every Pallas kernel of ``ray_tpu/ops`` asks of the mesh
it is traced under (``ops/mosaic.py``), held over all six ops that ask them:
under the batch's axes the call runs a batch shard each inside ``shard_map``
(``per_batch_shard``); under any other live axis ``impl=None`` answers the
twin (``takes_kernels``)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ray_tpu.ops import attention, conv, delta, ssm

_F32, _BF16 = jnp.float32, jnp.bfloat16

# op -> (the call, its operands' shapes at a toy size with a batch of 2,
# which of them are split by rows, the ``auto`` function, the shapes of the
# operands it reads, what it answers (kernels, twin))
_OPS = {
    "flash_attention": (
        lambda impl, q, k, v: attention.flash_attention(
            q, k, v, causal=True, impl=impl),
        [(2, 128, 64)] * 3, (True,) * 3,
        lambda q: attention.auto_attention(q), [(4, 1024, 12, 64)],
        ("flash", "xla")),
    "selective_scan": (
        lambda impl, *a: ssm.selective_scan(*a, chunk=16, impl=impl),
        [(2, 16, 128), (2, 16, 128), (128, 8), (2, 16, 8), (2, 16, 8),
         (128,)], (True, True, False, True, True, False),
        ssm.auto_impl, [(4, 32, 128), (128, 8)], ("pallas", "scan")),
    "ssd_scan": (
        lambda impl, *a: ssm.ssd_scan(*a, impl=impl),
        [(2, 128, 2, 64), (2, 128, 2), (2,), (2, 128, 1, 128),
         (2, 128, 1, 128), (2,)], (True, True, False, True, True, False),
        ssm.ssd_auto_impl, [(4, 128, 2, 64), (4, 128, 1, 128)],
        ("pallas", "scan")),
    "gated_short_conv": (
        lambda impl, bcx, taps: conv.gated_short_conv(bcx, taps, impl=impl),
        [(2, 32, 384), (3, 128)], (True, False),
        conv.auto_impl, [(4, 32, 384), (3, 128)], ("pallas", "jnp")),
    "causal_conv": (
        lambda impl, x, taps: conv.causal_conv(x, taps, jax.nn.silu,
                                               impl=impl),
        [(2, 64, 128), (4, 128)], (True, False),
        lambda x, taps: conv.causal_auto_impl(x, taps, jax.nn.silu),
        [(4, 64, 128), (4, 128)], ("pallas", "jnp")),
    "gated_delta_rule": (
        lambda impl, *a: delta.gated_delta_rule(*a, impl=impl),
        [(2, 128, 1, 128), (2, 128, 1, 128), (2, 128, 1, 128), (2, 128, 1),
         (2, 128, 1)], (True,) * 5,
        delta.auto_impl, [(4, 128, 1, 128)] * 2, ("pallas", "scan")),
}


def _operands(op, shapes):
    """Seeded operands an op takes as they are: decays negative, step sizes
    and mixing rates in (0, 1), keys of unit norm."""
    keys = jax.random.split(jax.random.PRNGKey(3), len(shapes))
    xs = [0.5 * jax.random.normal(k, s, _F32) for k, s in zip(keys, shapes)]
    if op in ("selective_scan", "ssd_scan"):
        xs[1], xs[2] = jax.nn.sigmoid(xs[1]), -jnp.exp(xs[2])
    if op == "gated_delta_rule":
        xs[1] = xs[1] / jnp.linalg.norm(xs[1], axis=-1, keepdims=True)
        xs[3], xs[4] = -jax.nn.sigmoid(xs[3]), jax.nn.sigmoid(xs[4])
    return xs


@pytest.mark.parametrize("op", _OPS)
def test_under_a_batch_axis_the_kernels_run_a_batch_shard_each(op):
    """Traced under a mesh whose ``data`` axis splits the batch, the call in
    interpret mode is a ``shard_map`` over the rows, comes back split by
    rows and equals the unsharded call."""
    call, shapes, split = _OPS[op][:3]
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    rows = NamedSharding(mesh, PartitionSpec("data"))
    xs = _operands(op, shapes)
    placed = [jax.device_put(x, rows) if s else x for x, s in zip(xs, split)]
    fn = jax.jit(lambda *a: call("pallas_interpret", *a))
    alone, sharded = fn.trace(*xs), fn.trace(*placed)
    assert "shard_map" not in str(alone.jaxpr)
    assert "shard_map" in str(sharded.jaxpr)
    want = alone.lower().compile()(*xs)
    got = sharded.lower().compile()(*placed)
    assert got.sharding.spec[0] == "data"
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("axes,shape,kernels", [
    # ``per_batch_shard`` maps the batch axes alone: under any other live
    # axis the partitioner would meet the Mosaic call and refuse it
    (("data", "model"), (2, 2), False),
    (("data", "model"), (4, 1), True),
])
@pytest.mark.parametrize("op", _OPS)
def test_auto_reads_the_mesh(monkeypatch, op, axes, shape, kernels):
    auto, shapes, answers = _OPS[op][3:]
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(shape), axes)
    placed = [jax.ShapeDtypeStruct(
        shapes[0], _BF16, sharding=NamedSharding(mesh, PartitionSpec("data")))]
    placed += [jax.ShapeDtypeStruct(s, _BF16) for s in shapes[1:]]
    seen = []
    jax.jit(lambda *a: seen.append(auto(*a))).lower(*placed)
    assert seen == [answers[0] if kernels else answers[1]]
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    jax.jit(lambda *a: seen.append(auto(*a))).lower(*placed)
    assert seen[1] == answers[1]


def test_a_batch_the_axes_do_not_divide_names_the_op_that_asked():
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    whole = NamedSharding(mesh, PartitionSpec())
    call, shapes = _OPS["selective_scan"][:2]
    odd = [jax.ShapeDtypeStruct((3,) + s[1:] if s[0] == 2 else s, _F32,
                                sharding=whole) for s in shapes]
    with pytest.raises(ValueError, match="selective_scan: leading dim 3 "):
        jax.jit(lambda *a: call("pallas_interpret", *a)).lower(*odd)

"""The selective scan (``ray_tpu/ops/ssm.py``): the kernels in interpret mode
and the chunked ``lax.scan`` against a position-by-position float32 loop,
forward and every gradient, over chunk lengths that do and do not divide the
sequence and a sequence shorter than a chunk; what the kernels are named and
write into the runtime's ring; what recomputation keeps of them."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import selective_scan, ssm
from ray_tpu.ops.remat import remat_policy
from tests.conftest import kernel_calls


def loop_reference(x, delta, a, b, c, skip):
    """The recurrence as written, one position a step, one sequence at a
    time: h_t = exp(delta_t A) h_{t-1} + (delta_t x_t) B_t^T; y_t = h_t C_t
    + D x_t."""

    def one(x, delta, b, c):
        def step(h, inputs):
            x_t, d_t, b_t, c_t = inputs
            h = (jnp.exp(d_t[:, None] * a) * h
                 + (d_t * x_t)[:, None] * b_t[None, :])
            return h, h @ c_t + skip * x_t

        return jax.lax.scan(step, jnp.zeros(a.shape, jnp.float32),
                            (x, delta, b, c))[1]

    return jax.vmap(one)(x, delta, b, c)


def _operands(batch, length, channels, states, dtype=jnp.float32, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 7)
    shape = (batch, length, channels)
    return (jax.random.normal(ks[0], shape, dtype),
            jax.nn.softplus(jax.random.normal(ks[1], shape) - 1.0),
            -jnp.exp(0.5 * jax.random.normal(ks[2], (channels, states))),
            jax.random.normal(ks[3], (batch, length, states), dtype),
            jax.random.normal(ks[4], (batch, length, states), dtype),
            jax.random.normal(ks[5], (channels,)),
            jax.random.normal(ks[6], shape))


# (batch, length, channels, states, chunk)
_CASES = {
    "chunks_divide": (2, 64, 256, 16, 32),
    "chunk_does_not_divide": (1, 40, 128, 8, 16),
    "shorter_than_a_chunk": (1, 24, 128, 16, 128),
    "one_chunk_two_channel_blocks": (1, 32, 1024, 8, 32),
    "length_no_multiple_of_16": (2, 21, 128, 8, 16),
}


@pytest.mark.parametrize("impl", ["scan", "pallas_interpret"])
@pytest.mark.parametrize("case", _CASES)
def test_scan_matches_the_loop(case, impl):
    batch, length, channels, states, chunk = _CASES[case]
    *ops, w = _operands(batch, length, channels, states)

    def out_and_grads(fn):
        out, pull = jax.vjp(fn, *ops)
        return (out, *pull(w))

    got = jax.jit(lambda: out_and_grads(
        lambda *o: selective_scan(*o, chunk=chunk, impl=impl)))()
    want = jax.jit(lambda: out_and_grads(loop_reference))()
    for name, a, b in zip(("y", "dx", "ddelta", "dA", "dB", "dC", "dD"),
                          got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(a, b, atol=2e-5 * scale, rtol=2e-5,
                                   err_msg=name)


@pytest.mark.parametrize("impl", ["scan", "pallas_interpret"])
def test_bfloat16_operands_keep_a_float32_state(impl):
    """x, B and C in bfloat16 (the model's operands), delta and A float32:
    the result is the float32 loop's on the same rounded operands, rounded
    once at the end; the gradients come back in the operands' dtypes."""
    *ops, w = _operands(1, 64, 128, 16, jnp.bfloat16, seed=3)
    f32 = lambda t: t.astype(jnp.float32)
    fn = lambda *o: f32(selective_scan(*o, chunk=32, impl=impl))
    out, pull = jax.vjp(fn, *ops)
    want, pull_want = jax.vjp(loop_reference, *map(f32, ops))
    assert float(jnp.abs(out - want).max() / jnp.abs(want).max()) < 5e-3
    for got, wanted, like in zip(pull(w), pull_want(w), ops):
        assert got.dtype == like.dtype
        assert float(jnp.abs(f32(got) - wanted).max()
                     / jnp.abs(wanted).max()) < 1e-2


def test_kernels_are_named_and_recorded():
    """The two ``pallas_call``s carry the names the benchmark's readers find
    them by, and each traced pass writes one ``ssm/scan`` record: what it
    walks and what its boundary states weigh."""
    from ray_tpu._private import steptrace

    shape = (1, 16384, 5120)
    x = jax.ShapeDtypeStruct(shape, jnp.bfloat16)
    d = jax.ShapeDtypeStruct(shape, jnp.float32)
    a = jax.ShapeDtypeStruct((5120, 16), jnp.float32)
    b = jax.ShapeDtypeStruct((1, 16384, 16), jnp.bfloat16)
    skip = jax.ShapeDtypeStruct((5120,), jnp.float32)
    grad = jax.grad(lambda *o: selective_scan(*o, impl="pallas").astype(
        jnp.float32).sum(), argnums=tuple(range(6)))
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        jax.clear_caches()  # the record is written where a call is traced
        jaxpr = jax.make_jaxpr(grad)(x, d, a, b, b, skip)
        records = [r["values"] for r in steptrace.snapshot()
                   if r["kind"] == "counters" and r["name"] == "ssm/scan"]
    finally:
        steptrace.set_enabled(False)
        jax.clear_caches()
    assert kernel_calls(jaxpr) == {"ssm_scan_fwd": 1, "ssm_scan_bwd": 1}
    assert {r["backward"] for r in records} == {0, 1}
    for r in records:
        assert r == {"channels": 5120, "states": 16, "tokens": 16384,
                     "chunk": 128, "chunks": 128, "backward": r["backward"],
                     "boundary_bytes": 128 * 16 * 5120 * 4}


def test_recomputation_keeps_the_scan():
    """Under ``ops.remat.remat_policy`` a recomputed function's
    backward pass holds the backward kernel and no second forward one: the
    output and the boundary states are kept by their names. Without the
    policy the forward kernel runs again."""
    *ops, _ = _operands(1, 32, 128, 8)

    def layer(*o):
        return jnp.tanh(selective_scan(*o, chunk=16,
                                       impl="pallas_interpret")).sum()

    def calls(policy):
        fn = jax.checkpoint(layer, policy=policy)
        jaxpr = jax.make_jaxpr(jax.grad(fn, argnums=tuple(range(6))))(*ops)
        return kernel_calls(jaxpr)

    assert calls(remat_policy()) == {"ssm_scan_fwd": 1, "ssm_scan_bwd": 1}
    assert calls(None) == {"ssm_scan_fwd": 2, "ssm_scan_bwd": 1}


def test_auto_takes_the_kernels_on_a_tpu_where_the_layout_fits(monkeypatch):
    x = jnp.zeros((1, 32, 256))
    a = jnp.zeros((256, 16))
    assert ssm.auto_impl(x, a) == "scan"            # this process: a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssm.auto_impl(x, a) == "pallas"
    assert ssm.auto_impl(jnp.zeros((1, 32, 200)), jnp.zeros((200, 16))) \
        == "scan"                                   # channels off the lanes
    assert ssm.auto_impl(x, jnp.zeros((256, 4))) == "scan"

"""The gated delta rule (``ray_tpu/ops/delta.py``): the kernels in interpret
mode and the chunked ``lax.scan`` against the recurrence one position a
step, forward and every gradient, over several chunks and groups, two
sequences, a length that is no multiple of the boundary stride (padded),
decays near 0 and near 1; what the kernels are named and write into the
runtime's ring; what recomputation keeps of them."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import delta
from ray_tpu.ops.remat import remat_policy
from ray_tpu.ops.delta import gated_delta_rule
from tests.conftest import kernel_calls


def loop_reference(q, k, v, g, beta):
    """The recurrence as written, one position a step, one sequence and one
    value head at a time: S' = exp(g_t) S; S = S' + k_t (beta_t (v_t - S'^T
    k_t))^T; o_t = S^T q_t."""
    rep = v.shape[2] // q.shape[2]
    q, k = jnp.repeat(q, rep, axis=2), jnp.repeat(k, rep, axis=2)

    def head(q, k, v, g, beta):
        def step(state, inputs):
            q_t, k_t, v_t, g_t, b_t = inputs
            state = jnp.exp(g_t) * state
            state = state + jnp.outer(k_t, b_t * (v_t - state.T @ k_t))
            return state, state.T @ q_t

        return jax.lax.scan(
            step, jnp.zeros((q.shape[-1], v.shape[-1]), jnp.float32),
            (q, k, v, g, beta))[1]

    over_heads = jax.vmap(head, in_axes=1, out_axes=1)
    return jax.vmap(over_heads)(q, k, v, g, beta)


def _operands(batch, length, key_heads, heads, d_k, d_v, dtype=jnp.float32,
              seed=0, decay=1.0):
    """q and k as a layer hands them over (unit length, q scaled), beta in
    (0, 1), g <= 0 with ``decay`` its typical size, and a cotangent."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    unit = lambda t: t / jnp.linalg.norm(t, axis=-1, keepdims=True)
    q = unit(jax.random.normal(ks[0], (batch, length, key_heads, d_k)))
    k = unit(jax.random.normal(ks[1], (batch, length, key_heads, d_k)))
    v = jax.random.normal(ks[2], (batch, length, heads, d_v))
    g = -decay * jax.nn.softplus(jax.random.normal(ks[3],
                                                   (batch, length, heads)))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (batch, length, heads)))
    w = jax.random.normal(ks[5], v.shape)
    return ((q * d_k ** -0.5).astype(dtype), k.astype(dtype), v.astype(dtype),
            g, beta, w)


# (batch, length, key heads, heads, d_k, d_v, chunk, decay): at float32 and
# d_k 128 the stride is one chunk of 32 rounded up to 128 positions (four
# chunks a group), at d_k 32 two chunks of 16. The cases of one shape share
# a compilation.
_SHAPE = (2, 128, 1, 2, 128, 128, 32)
_CASES = {
    "several_chunks_two_sequences": (*_SHAPE, 1.0),
    "decays_near_one": (*_SHAPE, 1e-3),
    "decays_near_zero": (*_SHAPE, 12.0),
    "two_key_heads_narrow_keys": (1, 64, 2, 2, 32, 128, 16, 1.0),
    "length_off_the_stride_is_padded": (2, 40, 1, 2, 128, 128, 16, 1.0),
    # a grid step is the whole groups of both key heads: every value head's
    # state and its gradient carried over three grid steps, one, two and
    # four value heads a key head (at chunk 16 the four share a lane tile's
    # pack)
    "one_value_head_a_key_head_three_groups": (1, 96, 2, 2, 32, 128, 16, 1.0),
    "two_value_heads_a_key_head_three_groups": (2, 96, 2, 4, 32, 128, 16,
                                                1.0),
    "four_value_heads_a_key_head_three_groups": (1, 96, 2, 8, 32, 128, 16,
                                                 1.0),
    "four_value_heads_off_the_stride": (1, 72, 2, 8, 32, 128, 16, 0.1),
    # chunk 64 as the model runs it: two value heads a lane tile, two packs
    # a step, two groups
    "four_value_heads_in_two_packs": (1, 256, 2, 8, 128, 128, 64, 1.0),
}
_NAMES = ("o", "dq", "dk", "dv", "dg", "dbeta")


@functools.lru_cache(maxsize=None)
def _out_and_grads(impl, chunk):
    """Jitted (q, k, v, g, beta, w) -> (o, the five gradients under the
    cotangent w), by the rule's ``impl`` or, for None, by the loop."""
    def fn(*xs):
        *ops, w = xs
        rule = loop_reference if impl is None else (
            lambda *o: gated_delta_rule(*o, chunk=chunk, impl=impl))
        out, pull = jax.vjp(rule, *ops)
        return (out, *pull(w))

    return jax.jit(fn)


@pytest.mark.parametrize("impl", ["scan", "pallas_interpret"])
@pytest.mark.parametrize("case", _CASES)
def test_rule_matches_the_loop(case, impl):
    *shape, chunk, decay = _CASES[case]
    xs = _operands(*shape, decay=decay)
    got = _out_and_grads(impl, chunk)(*xs)
    with jax.default_matmul_precision("highest"):
        want = _out_and_grads(None, None)(*xs)
    for name, a, b in zip(_NAMES, got, want):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(a, b, atol=1e-4 * scale, rtol=1e-4,
                                   err_msg=name)


def test_sequences_are_independent():
    """A sequence's output does not depend on what shares its batch: the
    state is zero at each sequence's start."""
    *ops, _ = _operands(2, 64, 1, 2, 128, 128, seed=1)
    both = gated_delta_rule(*ops, chunk=32, impl="scan")
    alone = gated_delta_rule(*(t[1:] for t in ops), chunk=32, impl="scan")
    np.testing.assert_allclose(both[1:], alone, atol=1e-6)


# (batch, length, key heads, heads, d_k, d_v, chunk) in bfloat16, where a
# boundary is kept every 256 positions: one, two and four value heads a key
# head over two groups and over a length off the stride
_BF16_CASES = {
    "two_on_one_padded": _SHAPE,
    "two_on_two_two_groups": (1, 512, 2, 2, 128, 128, 64),
    "four_on_two_two_groups": (1, 512, 2, 4, 128, 128, 64),
    "eight_on_two_off_the_stride": (1, 320, 2, 8, 128, 128, 64),
}


@pytest.mark.parametrize("impl", ["scan", "pallas_interpret"])
@pytest.mark.parametrize("case", _BF16_CASES)
def test_bfloat16_operands_keep_a_float32_state(impl, case):
    """q, k, v in bfloat16 (the model's operands), g and beta float32: the
    result is near the float32 loop's on the same rounded operands; the
    gradients come back in the operands' dtypes."""
    *shape, chunk = _BF16_CASES[case]
    *ops, w = _operands(*shape, jnp.bfloat16, seed=3, decay=0.1)
    f32 = lambda t: t.astype(jnp.float32)
    out, *grads = _out_and_grads(impl, chunk)(*ops, w.astype(jnp.bfloat16))
    want, *want_grads = _out_and_grads(None, None)(*map(f32, ops), w)
    assert out.shape == want.shape and out.dtype == jnp.bfloat16
    rel = lambda a, b: float(jnp.linalg.norm(f32(a) - b)
                             / jnp.linalg.norm(b))
    assert rel(out, want) < 1e-2
    for name, got, wanted, like in zip(_NAMES[1:], grads, want_grads, ops):
        assert got.dtype == like.dtype, name
        assert rel(got, wanted) < 3e-2, name


@pytest.mark.parametrize("chunk, pack, lefts", [(64, 2, 2), (64, 1, 1),
                                                (16, 4, 2), (32, 2, 1)])
def test_packed_split_products_are_the_split_product(chunk, pack, lefts):
    """``_packed_products`` of [C, pack x C] float32s (``pack`` [C, C]
    blocks side by side, several left factors against one right factor in
    two stacked passes) is ``_split_dot`` block by block: the same three
    terms summed in the same order beside exact zeros, so equal to float32
    rounding; and ``_split_dot`` holds both factors to 16 bits of mantissa
    (a single bfloat16 pass is a hundred times further off)."""
    ks = jax.random.split(jax.random.PRNGKey(chunk + pack), lefts + 1)
    draw = lambda key: jax.random.normal(key, (chunk, pack * chunk))
    right, *left = map(draw, ks)
    packing = delta._Packing(chunk, pack)
    got = delta._packed_products(left, right, packing, exact=False)
    exact = delta._packed_products(left, right, packing, exact=True)
    block = lambda t, u: t[:, u * chunk:(u + 1) * chunk]
    for l, g, e in zip(left, got, exact):
        assert g.shape == l.shape and g.dtype == jnp.float32
        for u in range(pack):
            a, b = block(l, u), block(right, u)
            want = delta._split_dot(a, b)
            scale = float(jnp.abs(want).max())
            np.testing.assert_allclose(block(g, u), want, atol=2e-6 * scale)
            full = jnp.dot(a, b, precision="highest")
            np.testing.assert_allclose(block(e, u), full, atol=2e-6 * scale)
            off = lambda t: float(jnp.linalg.norm(t - full)
                                  / jnp.linalg.norm(full))
            one_pass = jnp.dot(a.astype(jnp.bfloat16), b.astype(jnp.bfloat16),
                               preferred_element_type=jnp.float32)
            assert 100 * off(block(g, u)) < off(one_pass)


def test_boundaries_weigh_no_more_than_the_output():
    assert delta.stride_of(64, 128, 2) == 256     # bfloat16: every 4 chunks
    assert delta.stride_of(64, 128, 4) == 128
    assert delta.stride_of(16, 32, 4) == 32
    for chunk, d_k, size in ((64, 128, 2), (64, 128, 4), (32, 256, 2)):
        stride = delta.stride_of(chunk, d_k, size)
        assert stride % chunk == 0 and d_k * 4 <= stride * size


def _kernel_grids(jaxpr):
    """{a ``pallas_call``'s name: its grid} over ``jaxpr`` and what it
    calls."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = tuple(
                    eqn.params["grid_mapping"].grid)
            else:
                for sub in jax.core.jaxprs_in_params(eqn.params):
                    walk(sub)

    walk(jaxpr.jaxpr)
    return found


def test_kernels_are_named_and_recorded():
    """The two ``pallas_call``s carry the names the benchmark's readers find
    them by, one forward and one backward a rule call, over a grid of
    (batch, pairs of key heads, groups): a step is two key heads' whole
    groups. Each traced pass writes one ``delta/rule`` record: what it
    walks, what its boundary states weigh, what it has to move and a grid
    step's width."""
    from ray_tpu._private import steptrace

    bf16, f32 = jnp.bfloat16, jnp.float32
    qk = jax.ShapeDtypeStruct((4, 8192, 16, 128), bf16)
    v = jax.ShapeDtypeStruct((4, 8192, 32, 128), bf16)
    gate = jax.ShapeDtypeStruct((4, 8192, 32), f32)
    grad = jax.grad(lambda *o: gated_delta_rule(*o, impl="pallas").astype(
        f32).sum(), argnums=tuple(range(5)))
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        jax.clear_caches()  # the record is written where a call is traced
        jaxpr = jax.make_jaxpr(grad)(qk, qk, v, gate, gate)
        records = [r["values"] for r in steptrace.snapshot()
                   if r["kind"] == "counters" and r["name"] == "delta/rule"]
    finally:
        steptrace.set_enabled(False)
        jax.clear_caches()
    assert kernel_calls(jaxpr) == {"gated_delta_fwd": 1, "gated_delta_bwd": 1}
    assert _kernel_grids(jaxpr) == {"gated_delta_fwd": (4, 8, 32),
                                    "gated_delta_bwd": (4, 8, 32)}
    assert {r["backward"] for r in records} == {0, 1}
    tokens = 4 * 8192
    qk_bytes, v_bytes, gates = (tokens * 16 * 128 * 2, tokens * 32 * 128 * 2,
                                tokens * 32 * 4)
    for r in records:
        assert r == {
            "heads": 32, "key_heads": 16, "d_k": 128, "d_v": 128,
            "tokens": tokens, "sequences": 4, "chunk": 64,
            # a boundary every 256 positions: the output's bytes
            "boundary_bytes": v_bytes,
            "bytes_needed": (4 * qk_bytes + 3 * v_bytes + 4 * gates
                             if r["backward"]
                             else 2 * qk_bytes + 2 * v_bytes + 2 * gates),
            "backward": r["backward"],
            # two key heads x two value heads each x four chunks a group
            # of 256
            "problems_a_step": 16, "grid_steps": 4 * 8 * 32}


def test_recomputation_keeps_the_rule():
    """Under ``ops.remat.remat_policy`` a recomputed function's
    backward pass holds the backward kernel and no second forward one: the
    output and the boundary states are kept by their names. Without the
    policy the forward kernel runs again."""
    *ops, _ = _operands(1, 32, 1, 1, 128, 128)

    def layer(*o):
        return jnp.tanh(gated_delta_rule(*o, chunk=16,
                                         impl="pallas_interpret")).sum()

    def calls(policy):
        fn = jax.checkpoint(layer, policy=policy)
        jaxpr = jax.make_jaxpr(jax.grad(fn, argnums=tuple(range(5))))(*ops)
        return kernel_calls(jaxpr)

    assert calls(remat_policy()) == {"gated_delta_fwd": 1,
                                     "gated_delta_bwd": 1}
    assert calls(None) == {"gated_delta_fwd": 2, "gated_delta_bwd": 1}


def test_auto_takes_the_kernels_on_a_tpu_where_the_layout_fits(monkeypatch):
    q, v = jnp.zeros((1, 64, 2, 128)), jnp.zeros((1, 64, 4, 128))
    assert delta.auto_impl(q, v) == "scan"            # this process: a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert delta.auto_impl(q, v) == "pallas"
    assert delta.auto_impl(jnp.zeros((1, 64, 2, 64)), v) == "scan"
    assert delta.auto_impl(q, jnp.zeros((1, 64, 4, 96))) == "scan"

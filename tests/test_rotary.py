"""The heads' norm and the rotation as one function (``ops/rotary.py``): the
``jnp`` twin against the two modules it replaced, the kernel pair
(interpreted) against the twin and its ``vjp`` in both forms a cotangent
arrives in, the layer's one differentiable function
(``ops.attention.normed_rotary_self_attention``) against XLA's attention of
the modules' results, what the shapes' rule refuses, the mesh's two
questions, and the counter."""

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from ray_tpu._private import steptrace
from ray_tpu.models.afmoe import rotate_halves
from ray_tpu.models.llama import RMSNorm, rope_frequencies, rope_table
from ray_tpu.ops import attention, flash_kernels, rotary
from tests.conftest import kernel_calls

_F32, _BF16, EPS = jnp.float32, jnp.bfloat16, 1e-5
# a YaRN table whose ramp lies inside the head's dimensions and whose cos
# and sin carry an attention factor (0.1 ln 4 + 1)
_YARN = {"rope_type": "yarn", "rope_theta": 10000.0, "factor": 4.0,
         "original_max_position_embeddings": 16, "beta_fast": 32.0,
         "beta_slow": 1.0, "attention_factor": None}


def _table(kind, seq, d=128):
    """(cos, sin) [1, seq, d / 2], or (None, None) for a layer that is
    normed and not rotated."""
    positions = jnp.arange(seq)[None, :]
    if kind == "none":
        return None, None
    if kind == "plain":
        return rope_frequencies(d, positions, 10000.0)
    return rope_table(d, positions, _YARN)


def _modules(x, scale, cos, sin):
    """``rotate_halves(RMSNorm(x))``, the two modules the op replaced."""
    n = RMSNorm(EPS, x.dtype).apply({"params": {"scale": scale}}, x)
    return n if cos is None else rotate_halves(n, cos, sin)


def _operands(heads, seq=16, batch=2, dtype=_F32, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(keys[0], (batch, seq, heads, 128), _F32)
    scale = 1 + 0.1 * jax.random.normal(keys[1], (128,), _F32)
    g = jax.random.normal(keys[2], x.shape, _F32)
    return x.astype(dtype), scale, g.astype(dtype)


@pytest.mark.parametrize("kind", ["plain", "yarn", "none"])
def test_the_twin_is_the_norm_then_the_rotation(kind):
    """Values and the gradient for x and the scale, float32 on both sides,
    32 heads and 4; the YaRN table's attention factor is in cos and sin."""
    cos, sin = _table(kind, 16)
    if kind == "yarn":
        assert float(cos[0, 0, 0]) == pytest.approx(0.1 * np.log(4) + 1)
    for heads in (32, 4):
        x, scale, g = _operands(heads)
        want, want_vjp = jax.vjp(
            lambda x, s: _modules(x, s, cos, sin), x, scale)
        got, got_vjp = jax.vjp(
            lambda x, s: rotary.head_rotary(x, s, cos, sin, eps=EPS),
            x, scale)
        np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)
        for a, b in zip(got_vjp(g), want_vjp(g)):
            np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)


def test_the_twin_rounds_once():
    """In bfloat16 the twin rounds the float32 result once, where the two
    modules rounded the normed value, widened it and rounded again: equal
    to the float32 result's rounding to the bit."""
    cos, sin = _table("plain", 16)
    x, scale, _ = _operands(4, dtype=_BF16)
    exact = rotary.head_rotary(x.astype(_F32), scale, cos, sin, eps=EPS)
    got = rotary.head_rotary(x, scale, cos, sin, eps=EPS)
    assert got.dtype == _BF16
    np.testing.assert_array_equal(got, exact.astype(_BF16))


@pytest.mark.parametrize("dtype", [_F32, _BF16], ids=["f32", "bf16"])
@pytest.mark.parametrize("kind", ["yarn", "none"])
@pytest.mark.parametrize("heads", [32, 4, 3])
def test_the_kernels_are_the_twin(heads, kind, dtype):
    """``head_rotary_fwd`` writes the twin's result as the flash kernels'
    [B x H, T, 128]; ``head_rotary_bwd`` is the twin's ``vjp`` from a
    cotangent in either form it arrives in: the flash backward's float32
    [B x H, 128, T] sum (turned in the kernel) and the model's [B, T, H x
    128]. 32 heads in steps of four, 4 in one, 3 in one."""
    batch, seq = 2, 16
    cos, sin = _table(kind, seq)
    flat = (None, None) if cos is None else rotary.tables(cos, sin)
    x, scale, g = _operands(heads, seq, batch, dtype)
    lanes = x.reshape(batch, seq, heads * 128)
    want, vjp = jax.vjp(
        lambda x, s: rotary.head_rotary(x, s, cos, sin, eps=EPS), x, scale)
    got = rotary.head_rotary_fwd(lanes, scale, *flat, heads=heads, eps=EPS,
                                 interpret=True)
    assert got.shape == (batch * heads, seq, 128) and got.dtype == dtype
    tight = dict(rtol=1e-5, atol=1e-5) if dtype == _F32 else dict(
        rtol=1e-2, atol=1e-2)
    np.testing.assert_allclose(
        got.reshape(batch, heads, seq, 128).transpose(0, 2, 1, 3).astype(
            _F32), want.astype(_F32), **tight)
    want_dx, want_dscale = vjp(g)
    turned = g.astype(_F32).transpose(0, 2, 3, 1).reshape(
        batch * heads, 128, seq)
    for cotangent, is_turned in ((turned, True), (g.reshape(lanes.shape),
                                                  False)):
        dx, dscale = rotary.head_rotary_bwd(
            cotangent, lanes, scale, *flat, heads=heads, eps=EPS,
            turned=is_turned, interpret=True)
        assert dx.shape == lanes.shape and dx.dtype == dtype
        assert dscale.shape == (128,) and dscale.dtype == _F32
        np.testing.assert_allclose(
            dx.reshape(x.shape).astype(_F32), want_dx.astype(_F32), **tight)
        np.testing.assert_allclose(dscale, want_dscale, rtol=1e-4,
                                   atol=1e-3)


@pytest.fixture
def four_blocks_of_keys(monkeypatch):
    """32 tokens are four blocks of keys a head: the ``model_results``
    boundary at a toy length, a window of 8 a block of its own."""
    monkeypatch.setattr(flash_kernels, "_MAX_RESIDENT", 8)
    monkeypatch.setattr(flash_kernels, "_WINDOW_RESIDENT_FROM", 8)
    jax.clear_caches()
    yield
    jax.clear_caches()


def _layer(heads=32, kv_heads=4, seq=32, batch=1, seed=1):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    shape = lambda n: (batch, seq, n, 128)
    return (jax.random.normal(keys[0], shape(heads), _F32),
            jax.random.normal(keys[1], shape(kv_heads), _F32),
            jax.random.normal(keys[2], shape(kv_heads), _F32),
            1 + 0.1 * jax.random.normal(keys[3], (128,), _F32),
            1 + 0.1 * jax.random.normal(keys[4], (128,), _F32))


@pytest.mark.parametrize("window", [None, 8], ids=["full", "w8"])
@pytest.mark.parametrize("kind", ["yarn", "none"])
def test_the_layer_is_xlas_attention_of_the_modules_results(
        four_blocks_of_keys, kind, window):
    """The one ``custom_vjp`` over the prologue's kernels and the flash
    pair (interpreted), 32 query heads on 4: the output and the gradient
    for q, k, v and both scales against ``rotate_halves(RMSNorm(.))`` under
    XLA's attention, float32."""
    cos, sin = _table(kind, 32)
    operands = _layer()

    def kernels(q, k, v, q_scale, k_scale):
        return attention.normed_rotary_self_attention(
            q, k, v, q_scale, k_scale, cos, sin, eps=EPS, attention="flash",
            window=window, impl="pallas_interpret", block_q=8, block_k=8)

    def modules(q, k, v, q_scale, k_scale):
        return attention.causal_self_attention(
            _modules(q, q_scale, cos, sin), _modules(k, k_scale, cos, sin),
            v, "xla", window)

    mix = jax.random.normal(jax.random.PRNGKey(7), operands[0].shape, _F32)
    with jax.default_matmul_precision("highest"):
        got, got_vjp = jax.vjp(kernels, *operands)
        want, want_vjp = jax.vjp(modules, *operands)
        got_grads, want_grads = got_vjp(mix), want_vjp(mix)
    assert kernel_calls(jax.make_jaxpr(
        lambda *a: jax.vjp(kernels, *a)[1](mix))(*operands)) == {
        "head_rotary_fwd": 2, "head_rotary_bwd": 2,
        flash_kernels._kernel_name("flash_fwd", window): 1,
        flash_kernels._kernel_name("flash_bwd", window): 1}
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)
    for name, a, b in zip("q k v q_scale k_scale".split(), got_grads,
                          want_grads):
        scale = float(jnp.abs(b).max())
        np.testing.assert_allclose(a, b, rtol=2e-3, atol=2e-4 * scale,
                                   err_msg=name)


def _said(fn, *shapes):
    """The ``attention/head_rotary`` records of tracing ``fn``."""
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        jax.eval_shape(fn, *shapes)
        return [r["values"] for r in steptrace.snapshot()
                if r["kind"] == "counters"
                and r["name"] == "attention/head_rotary"]
    finally:
        steptrace.set_enabled(False)


@pytest.fixture
def on_tpu(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    yield
    jax.clear_caches()


def _layer_shapes(d, rotary_dims, seq=4096, heads=8, kv_heads=2):
    half = jax.ShapeDtypeStruct((1, seq, rotary_dims // 2), _F32)
    x = lambda n: jax.ShapeDtypeStruct((2, seq, n, d), _BF16)
    scale = jax.ShapeDtypeStruct((d,), _F32)
    return x(heads), x(kv_heads), x(kv_heads), scale, scale, half, half


def test_a_head_of_64_and_a_partial_rotation_are_the_twins(on_tpu):
    """On a TPU at 4,096 tokens (two blocks of keys a head) a head of 128
    rotated over all of its width, or normed alone, takes the kernels; a
    head of 64 (half a lane tile), a table for 64 of a head's 128
    dimensions and a table a row of the batch are refused to the twin, as is
    one block of keys a head: no kernel of the prologue in the jaxpr, and
    the record says so."""
    entry = functools.partial(attention.normed_rotary_self_attention,
                              eps=EPS)
    q, k, v, scale, _, half, _ = _layer_shapes(128, 128)
    assert attention.auto_head_rotary(q, v, half) == "pallas"
    assert attention.auto_head_rotary(q, v, None) == "pallas"
    taken = _said(entry, *_layer_shapes(128, 128))
    assert taken == [{"tokens": 4096, "heads": 8, "kv_heads": 2,
                      "head_dim": 128, "rotated": 1, "kernel": 1}]
    assert _said(lambda q, k, v, a, b: entry(q, k, v, a, b, None, None),
                 q, k, v, scale, scale)[0]["rotated"] == 0
    a_row = jax.ShapeDtypeStruct((2, 4096, 64), _F32)
    refused = {
        "a head of 64": _layer_shapes(64, 64),
        "a partial rotation": _layer_shapes(128, 64),
        "a table a row": (q, k, v, scale, scale, a_row, a_row),
        "one block of keys": _layer_shapes(128, 128, seq=2048)}
    for why, shapes in refused.items():
        assert attention.auto_head_rotary(
            shapes[0], shapes[2], shapes[5]) == "jnp", why
    # a partial rotation is no call of this entry's twin either: its caller
    # keeps ``rotate_halves`` over the lanes it turns (models/qwen3_next.py)
    for why in ("a head of 64", "a table a row", "one block of keys"):
        assert [r["kernel"] for r in _said(entry, *refused[why])] == [0], why
        calls = kernel_calls(jax.make_jaxpr(entry)(*refused[why]))
        assert calls and not any(
            name.startswith("head_rotary") for name in calls), (why, calls)


def test_off_a_tpu_the_twin_runs_and_the_record_says_so():
    shapes = _layer_shapes(128, 128)
    assert attention.auto_head_rotary(shapes[0], shapes[2],
                                      shapes[5]) == "jnp"
    assert _said(functools.partial(attention.normed_rotary_self_attention,
                                   eps=EPS), *shapes) == [
        {"tokens": 4096, "heads": 8, "kv_heads": 2, "head_dim": 128,
         "rotated": 1, "kernel": 0}]


@pytest.mark.parametrize("axes,shape,kernels", [
    (("data", "model"), (2, 2), False),
    (("data", "model"), (4, 1), True),
])
def test_auto_reads_the_mesh(on_tpu, axes, shape, kernels):
    """As ``tests/test_ops_mesh.py`` asks of the six ops with an ``auto`` of
    their own: under any live axis but the batch's the twin."""
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(shape), axes)
    q, _, v, _, _, half, _ = _layer_shapes(128, 128, heads=4)
    q = jax.ShapeDtypeStruct((4, *q.shape[1:]), _BF16, sharding=NamedSharding(
        mesh, PartitionSpec("data")))
    seen = []
    jax.jit(lambda *a: seen.append(attention.auto_head_rotary(*a))).lower(
        q, v, half)
    assert seen == ["pallas" if kernels else "jnp"]


def test_under_a_batch_axis_the_kernels_run_a_batch_shard_each(
        four_blocks_of_keys):
    """Traced under a mesh whose ``data`` axis splits the batch, the layer
    in interpret mode is a ``shard_map`` over the rows, comes back split by
    rows and equals the unsharded call, gradient for the scales (summed over
    the shards) included."""
    cos, sin = _table("plain", 32)
    operands = _layer(heads=4, kv_heads=2, batch=2)
    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    rows = NamedSharding(mesh, PartitionSpec("data"))
    placed = [jax.device_put(x, rows) for x in operands[:3]] + list(
        operands[3:])

    def layer(*a):
        return attention.normed_rotary_self_attention(
            *a, cos, sin, eps=EPS, attention="flash",
            impl="pallas_interpret", block_q=8, block_k=8)

    fn = jax.jit(lambda *a: jax.value_and_grad(
        lambda *a: jnp.sum(jnp.sin(layer(*a))), argnums=(0, 3, 4))(*a))
    alone, sharded = fn.trace(*operands), fn.trace(*placed)
    assert "shard_map" not in str(alone.jaxpr)
    assert "shard_map" in str(sharded.jaxpr)
    want = alone.lower().compile()(*operands)
    got = sharded.lower().compile()(*placed)
    assert got[1][0].sharding.spec[0] == "data"
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-4)

"""The plain causal convolution with its activation
(``ray_tpu/ops/conv.py:causal_conv``): the kernels ``causal_conv_fwd`` /
``causal_conv_bwd`` in interpret mode against a position-by-position float64
loop and against the XLA form, for ``y``, ``dx`` and ``dtaps``, over several
sequences a batch, several blocks a sequence (the backward hands ``ds`` from
block to block), several loop steps a block and several slabs of channels;
what the custom_vjp keeps, what the kernels are named and write into the
runtime's ring, and where ``impl=None`` takes them."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import causal_conv, conv
from ray_tpu.ops.remat import remat_policy
from tests.conftest import kernel_calls


def _silu(s):
    return s / (1.0 + np.exp(-s))


def _silu_slope(s):
    gate = 1.0 / (1.0 + np.exp(-s))
    return gate * (1.0 + s * (1.0 - gate))


def loop_reference(x, taps, dy, silu, bias=None):
    """y, dx, dtaps (and, with a ``bias``, dbias) one position at a time,
    each sequence on its own, in float64."""
    x, taps, dy = (np.asarray(t, np.float64) for t in (x, taps, dy))
    batch, length, _ = x.shape
    k = taps.shape[0]
    y, dx, dtaps = np.zeros_like(x), np.zeros_like(x), np.zeros_like(taps)
    dbias = np.zeros_like(taps[0])
    for n in range(batch):
        for t in range(length):
            held = [(j, t - (k - 1) + j) for j in range(k)
                    if t - (k - 1) + j >= 0]
            s = sum(taps[j] * x[n, src] for j, src in held)
            if bias is not None:
                s = s + np.asarray(bias, np.float64)
            y[n, t] = _silu(s) if silu else s
            ds = dy[n, t] * (_silu_slope(s) if silu else 1.0)
            dbias += ds
            for j, src in held:
                dx[n, src] += taps[j] * ds
                dtaps[j] += ds * x[n, src]
    return (y, dx, dtaps) if bias is None else (y, dx, dtaps, dbias)


def _operands(batch, length, channels, k, dtype=jnp.float32, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    shape = (batch, length, channels)
    return (jax.random.normal(keys[0], shape, jnp.float32).astype(dtype),
            jax.random.normal(keys[1], (k, channels), jnp.float32),
            jax.random.normal(keys[2], shape, jnp.float32).astype(dtype))


def _out_and_grads(impl, x, taps, dy, activation):
    y, pull = jax.vjp(lambda a, w: causal_conv(a, w, activation, impl=impl),
                      x, taps)
    return (y, *pull(dy))


@pytest.fixture
def blocks(monkeypatch):
    """-> set(block bytes): small blocks give a short sequence several of
    them."""
    def set_to(block_bytes):
        monkeypatch.setattr(conv, "_BLOCK_BYTES", block_bytes)
        jax.clear_caches()
    yield set_to
    jax.clear_caches()


# (batch, length, channels, bytes of a block): one block of one loop step;
# one block of several steps; three blocks of one step (the rows before a
# block come from its neighbour, ``ds`` of the rows after it from the grid
# step before); four blocks of two steps over two slabs of 256 channels;
# channels that are no whole slab of 256 lanes (three of 128)
_CASES = [(2, 32, 128, 2**20), (3, 128, 128, 2**20), (2, 96, 128, 2**14),
          (2, 256, 512, 2**16), (2, 64, 384, 2**20)]
_IDS = lambda c: "x".join(map(str, c))


@pytest.mark.parametrize("activation", [None, jax.nn.silu],
                         ids=["none", "silu"])
@pytest.mark.parametrize("k", [2, 3, 4])
@pytest.mark.parametrize("case", _CASES, ids=_IDS)
def test_the_kernels_match_the_loop(case, k, activation, blocks):
    batch, length, channels, block_bytes = case
    blocks(block_bytes)
    x, taps, dy = _operands(batch, length, channels, k, seed=length + k)
    got = _out_and_grads("pallas_interpret", x, taps, dy, activation)
    want = loop_reference(x, taps, dy, activation is not None)
    for name, a, b in zip(("y", "dx", "dtaps"), got, want):
        np.testing.assert_allclose(np.asarray(a, np.float64), b, rtol=2e-5,
                                   atol=2e-5 * np.abs(b).max(), err_msg=name)


@pytest.mark.parametrize("activation", [None, jax.nn.silu],
                         ids=["none", "silu"])
@pytest.mark.parametrize("k", [2, 3, 4])
@pytest.mark.parametrize("case", _CASES[2:4], ids=_IDS)
def test_the_kernels_match_the_xla_form_in_float32(case, k, activation,
                                                   blocks):
    batch, length, channels, block_bytes = case
    blocks(block_bytes)
    xs = _operands(batch, length, channels, k, seed=k)
    for name, a, b in zip(("y", "dx", "dtaps"),
                          _out_and_grads("pallas_interpret", *xs, activation),
                          _out_and_grads("jnp", *xs, activation)):
        np.testing.assert_allclose(a, b, rtol=2e-5,
                                   atol=2e-5 * np.abs(b).max(), err_msg=name)


@pytest.mark.parametrize("activation", [None, jax.nn.silu],
                         ids=["none", "silu"])
@pytest.mark.parametrize("k", [2, 3, 4])
@pytest.mark.parametrize("channels", [128, 256, 384])
def test_bfloat16_operands_sum_in_float32_and_round_once(channels, k,
                                                         activation, blocks):
    """bfloat16 in, bfloat16 ``y`` and ``dx`` a rounding from the loop on the
    same (exactly representable) operands; ``dtaps`` float32. The XLA form's
    ``y`` is the same rounding of the same sum; its ``dx`` rounds each tap's
    share and adds them in bfloat16, so it lies further from the loop than
    the kernels'."""
    blocks(2**13)
    x, taps, dy = _operands(2, 64, channels, k, jnp.bfloat16, seed=channels)
    y, dx, dtaps = _out_and_grads("pallas_interpret", x, taps, dy, activation)
    assert y.dtype == dx.dtype == jnp.bfloat16 and dtaps.dtype == jnp.float32
    want = loop_reference(x, taps, dy, activation is not None)
    for name, a, b in zip(("y", "dx"), (y, dx), want):
        np.testing.assert_allclose(np.asarray(a, np.float64), b,
                                   atol=2 ** -8 * np.abs(b).max(),
                                   rtol=2 ** -8, err_msg=name)
    np.testing.assert_allclose(dtaps, want[2], rtol=1e-5,
                               atol=1e-5 * np.abs(want[2]).max())
    xla = _out_and_grads("jnp", x, taps, dy, activation)
    off = lambda t: np.abs(np.asarray(t[1], np.float64) - want[1]).max()
    assert np.abs(np.asarray(y, np.float32)
                  - np.asarray(xla[0], np.float32)).max() <= (
        2 ** -7 * np.abs(want[0]).max())
    assert off((y, dx)) <= off(xla)


@pytest.mark.parametrize("activation", [None, jax.nn.silu],
                         ids=["none", "silu"])
def test_nothing_crosses_a_sequences_start_or_end(activation, blocks):
    """Two sequences of three blocks a batch against each of them alone: to
    the bit. A forward that fetched the rows before a block across the
    batch's rows would fail the second sequence's first positions; a
    backward that kept the first sequence's ``ds`` in its scratch would fail
    the second sequence's last."""
    blocks(2**14)
    x, taps, dy = _operands(2, 96, 128, 4, seed=3)
    together = _out_and_grads("pallas_interpret", x, taps, dy, activation)
    alone = [_out_and_grads("pallas_interpret", x[n:n + 1], taps,
                            dy[n:n + 1], activation) for n in range(2)]
    for n in range(2):
        np.testing.assert_array_equal(together[0][n], alone[n][0][0])
        np.testing.assert_array_equal(together[1][n], alone[n][1][0])
    np.testing.assert_allclose(together[2], alone[0][2] + alone[1][2],
                               rtol=1e-6, atol=1e-5)


def test_the_residuals_are_the_two_arguments():
    """Nothing the size of ``y`` is kept for the backward pass: the
    custom_vjp's residuals are ``x`` and the taps, by either form."""
    from jax._src.ad_checkpoint import saved_residuals

    x, taps, _ = _operands(2, 64, 128, 4)
    for impl in ("jnp", "pallas_interpret"):
        kept = saved_residuals(
            lambda a, w: causal_conv(a, w, jax.nn.silu, impl=impl).sum(),
            x, taps)
        assert sorted(aval.shape for aval, _ in kept) == sorted(
            [x.shape, taps.shape]), kept


def test_kernels_are_named_and_recorded():
    """The two ``pallas_call``s carry their names (which the gated pair's
    readers, ``short_conv_(fwd|bwd)``, do not match), and each traced pass
    writes one ``conv/causal`` record; the XLA form writes none."""
    from perfbench.metrics.short_conv_ms import KERNEL
    from ray_tpu._private import steptrace

    x = jax.ShapeDtypeStruct((2, 8192, 8192), jnp.bfloat16)
    taps = jax.ShapeDtypeStruct((4, 8192), jnp.float32)
    grad = lambda impl: jax.grad(
        lambda a, w: causal_conv(a, w, jax.nn.silu, impl=impl).astype(
            jnp.float32).sum(), argnums=(0, 1))
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        jax.clear_caches()  # the record is written where a call is traced
        assert not kernel_calls(jax.make_jaxpr(grad("jnp"))(x, taps))
        assert not [r for r in steptrace.snapshot() if r["kind"] == "counters"]
        jaxpr = jax.make_jaxpr(grad("pallas"))(x, taps)
        records = [r["values"] for r in steptrace.snapshot()
                   if r["kind"] == "counters" and r["name"] == "conv/causal"]
    finally:
        steptrace.set_enabled(False)
        jax.clear_caches()
    assert kernel_calls(jaxpr) == {"causal_conv_fwd": 1, "causal_conv_bwd": 1}
    for name in kernel_calls(jaxpr):
        assert not KERNEL.match(
            f'%{name}.1 = bf16[2] custom-call(), '
            'custom_call_target="tpu_custom_call"')
    assert {r["backward"] for r in records} == {0, 1}
    cells = 2 * 8192 * 8192 * 2
    for r in records:
        assert r == {"channels": 8192, "taps": 4, "tokens": 2 * 8192,
                     "sequences": 2, "activation": 1, "bias": 0,
                     "backward": r["backward"],
                     "bytes_needed": (3 * cells + 2 * 4 * 8192 * 4
                                      if r["backward"]
                                      else 2 * cells + 4 * 8192 * 4)}
    assert conv.block_rows(8192, conv._slab(8192), 2) == 2048


def test_recomputation_runs_the_forward_kernel_again():
    """Under ``ops.remat.remat_policy`` a recomputed layer makes the
    convolution's output again (nothing of it is named for the policy): two
    forward kernels and one backward in the gradient."""
    x, taps, _ = _operands(1, 32, 128, 4)

    def layer(a, w):
        return jnp.tanh(causal_conv(a * 2.0, w, jax.nn.silu,
                                    impl="pallas_interpret")).sum()

    fn = jax.checkpoint(layer, policy=remat_policy())
    jaxpr = jax.make_jaxpr(jax.grad(fn, argnums=(0, 1)))(x, taps)
    assert kernel_calls(jaxpr) == {"causal_conv_fwd": 2, "causal_conv_bwd": 1}


def test_auto_takes_the_kernels_on_a_tpu_where_the_call_fits(monkeypatch):
    x, taps = jnp.zeros((2, 64, 256)), jnp.zeros((4, 256))
    silu = jax.nn.silu
    assert conv.causal_auto_impl(x, taps, silu) == "jnp"   # a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for k in (2, 3, 4):
        for activation in (None, silu):
            assert conv.causal_auto_impl(x, jnp.zeros((k, 256)),
                                         activation) == "pallas"
    # a length that is no whole number of loop steps, channels off the lanes,
    # one tap or five, an activation the kernels do not know (a SiLU written
    # out again is one): the XLA form's
    assert conv.causal_auto_impl(jnp.zeros((2, 40, 256)), taps, silu) == "jnp"
    assert conv.causal_auto_impl(jnp.zeros((2, 64, 200)),
                                 jnp.zeros((4, 200)), silu) == "jnp"
    assert conv.causal_auto_impl(x, jnp.zeros((1, 256)), silu) == "jnp"
    assert conv.causal_auto_impl(x, jnp.zeros((5, 256)), silu) == "jnp"
    assert conv.causal_auto_impl(x, taps, jnp.tanh) == "jnp"
    assert conv.causal_auto_impl(
        x, taps, lambda s: s * jax.nn.sigmoid(s)) == "jnp"


@pytest.mark.parametrize("what", ["length_40", "length_7", "five_taps",
                                  "tanh"])
def test_what_the_kernels_do_not_take_runs_the_xla_form(what, monkeypatch):
    """``impl=None`` on a TPU for a length ``_ROWS`` does not divide, five
    taps or an unknown activation: no kernel in the program, and the loop's
    values."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    length = {"length_40": 40, "length_7": 7}.get(what, 32)
    k = 5 if what == "five_taps" else 4
    activation = jnp.tanh if what == "tanh" else jax.nn.silu
    x, taps, dy = _operands(2, length, 128, k, seed=length)
    fn = lambda a, w: causal_conv(a, w, activation)
    assert not kernel_calls(jax.make_jaxpr(jax.grad(
        lambda a, w: fn(a, w).sum(), argnums=(0, 1)))(x, taps))
    y, pull = jax.vjp(fn, x, taps)
    if what == "tanh":
        want = jax.vjp(lambda a, w: jnp.tanh(causal_conv(a, w, impl="jnp")),
                       x, taps)
        want = (want[0], *want[1](dy))
    else:
        want = loop_reference(x, taps, dy, True)
    for a, b in zip((y, *pull(dy)), want):
        np.testing.assert_allclose(np.asarray(a, np.float64),
                                   np.asarray(b, np.float64), rtol=2e-5,
                                   atol=2e-5 * np.abs(b).max())
    jax.clear_caches()


def test_a_call_the_kernels_do_not_take_cannot_force_them():
    x, taps, _ = _operands(1, 40, 128, 4)
    with pytest.raises(AssertionError):
        causal_conv(x, taps, jax.nn.silu, impl="pallas_interpret")


def test_under_a_batch_axis_the_call_is_a_shard_map_over_rows():
    """Traced under a mesh whose ``data`` axis splits the batch, the kernels
    run a shard of sequences each: the values of the unsharded call."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    mesh = Mesh(np.array(jax.devices()[:2]), ("data",))
    x, taps, dy = _operands(4, 64, 128, 4, seed=11)
    want = _out_and_grads("pallas_interpret", x, taps, dy, jax.nn.silu)
    rows = NamedSharding(mesh, PartitionSpec("data"))
    sharded = jax.jit(lambda x, taps, dy: _out_and_grads(
        "pallas_interpret", x, taps, dy, jax.nn.silu))
    args = (jax.device_put(x, rows), taps, jax.device_put(dy, rows))
    assert "shard_map" in str(jax.make_jaxpr(sharded)(*args))
    got = sharded(*args)
    assert got[0].sharding.spec[0] == got[1].sharding.spec[0] == "data"
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-6, atol=1e-5)


# ---------------------------------------------------------------------------
# with a bias (PR 58): a number a channel added to the float32 sum before the
# activation; the kernels take it as one row more of the taps' array
# ---------------------------------------------------------------------------

def _with_bias(impl, x, taps, bias, dy, activation):
    y, pull = jax.vjp(
        lambda a, w, b: causal_conv(a, w, activation, b, impl=impl),
        x, taps, bias)
    return (y, *pull(dy))


def _bias(channels, seed):
    return jax.random.normal(jax.random.PRNGKey(100 + seed), (channels,))


@pytest.mark.parametrize("activation", [None, jax.nn.silu],
                         ids=["none", "silu"])
@pytest.mark.parametrize("k", [2, 4])
@pytest.mark.parametrize("case", _CASES[2:4], ids=_IDS)
def test_with_a_bias_the_kernels_match_the_loop(case, k, activation, blocks):
    """``y``, ``dx``, ``dtaps`` and ``dbias`` (the sum of ``ds``) over
    several blocks a sequence, several steps a block and two slabs."""
    batch, length, channels, block_bytes = case
    blocks(block_bytes)
    x, taps, dy = _operands(batch, length, channels, k, seed=length + k)
    bias = _bias(channels, k)
    want = loop_reference(x, taps, dy, activation is not None, bias)
    for impl in ("pallas_interpret", "jnp"):
        got = _with_bias(impl, x, taps, bias, dy, activation)
        for name, a, b in zip(("y", "dx", "dtaps", "dbias"), got, want):
            np.testing.assert_allclose(
                np.asarray(a, np.float64), b, rtol=2e-5,
                atol=2e-5 * np.abs(b).max(), err_msg=f"{impl} {name}")


def test_with_a_bias_bfloat16_operands_round_once(blocks):
    blocks(2**13)
    x, taps, dy = _operands(2, 64, 256, 4, jnp.bfloat16, seed=5)
    bias = _bias(256, 1)
    y, dx, dtaps, dbias = _with_bias("pallas_interpret", x, taps, bias, dy,
                                     jax.nn.silu)
    assert y.dtype == dx.dtype == jnp.bfloat16
    assert dtaps.dtype == dbias.dtype == jnp.float32
    want = loop_reference(x, taps, dy, True, bias)
    for name, a, b in zip(("y", "dx"), (y, dx), want):
        np.testing.assert_allclose(np.asarray(a, np.float64), b,
                                   atol=2 ** -8 * np.abs(b).max(),
                                   rtol=2 ** -8, err_msg=name)
    for a, b in ((dtaps, want[2]), (dbias, want[3])):
        np.testing.assert_allclose(a, b, rtol=1e-5,
                                   atol=1e-5 * np.abs(b).max())


def test_with_a_bias_the_record_says_so_and_one_kernel_pair_runs():
    from ray_tpu._private import steptrace

    x = jax.ShapeDtypeStruct((2, 8192, 6144), jnp.bfloat16)
    taps = jax.ShapeDtypeStruct((4, 6144), jnp.float32)
    bias = jax.ShapeDtypeStruct((6144,), jnp.float32)
    grad = jax.grad(lambda a, w, b: causal_conv(
        a, w, jax.nn.silu, b, impl="pallas").astype(jnp.float32).sum(),
        argnums=(0, 1, 2))
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        jax.clear_caches()
        jaxpr = jax.make_jaxpr(grad)(x, taps, bias)
        records = [r["values"] for r in steptrace.snapshot()
                   if r["kind"] == "counters" and r["name"] == "conv/causal"]
    finally:
        steptrace.set_enabled(False)
        jax.clear_caches()
    assert kernel_calls(jaxpr) == {"causal_conv_fwd": 1, "causal_conv_bwd": 1}
    cells = 2 * 8192 * 6144 * 2
    assert {r["backward"] for r in records} == {0, 1}
    for r in records:
        assert r["bias"] == 1 and r["taps"] == 4
        assert r["bytes_needed"] == conv.causal_needed_bytes(
            2 * 8192, 6144, 4, 2, bool(r["backward"]), bias=True) == (
            3 * cells + 2 * 5 * 6144 * 4 if r["backward"]
            else 2 * cells + 5 * 6144 * 4)


# sha256 (16 hex digits) of the jaxpr of the call WITHOUT a bias and of its
# gradient, function addresses struck out, as the parent of PR 58 traces
# them: the argument left out is the program it was
_HELD_WITHOUT_A_BIAS = {"pallas": "21dcb4bee2f31676", "jnp": "cdfccc35a0b2f849"}


@pytest.mark.parametrize("impl", ["pallas", "jnp"])
def test_without_a_bias_the_call_traces_to_the_held_jaxpr(impl):
    import hashlib
    import re

    x = jax.ShapeDtypeStruct((2, 8192, 8192), jnp.bfloat16)
    taps = jax.ShapeDtypeStruct((4, 8192), jnp.float32)
    fn = lambda a, w: causal_conv(a, w, jax.nn.silu, impl=impl)
    both = lambda a, w, dy: (fn(a, w), *jax.vjp(fn, a, w)[1](dy))
    text = re.sub(r" at 0x[0-9a-f]+", "", str(jax.make_jaxpr(both)(x, taps, x)))
    digest = hashlib.sha256(text.encode()).hexdigest()[:16]
    assert digest == _HELD_WITHOUT_A_BIAS[impl], digest
    # bias=None spelled out is the same call
    spelled = lambda a, w, dy: (
        causal_conv(a, w, jax.nn.silu, None, impl=impl),
        *jax.vjp(lambda a, w: causal_conv(a, w, jax.nn.silu, None,
                                          impl=impl), a, w)[1](dy))
    assert re.sub(r" at 0x[0-9a-f]+", "", str(
        jax.make_jaxpr(spelled)(x, taps, x))) == text

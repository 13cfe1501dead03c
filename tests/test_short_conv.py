"""The gated short convolution (``ray_tpu/ops/conv.py``): the kernels in
interpret mode and the ``jnp`` form against a position-by-position float32
loop, for ``y``, ``dbcx`` and ``dtaps``, over several sequences a batch (a
row that crossed a sequence's start would show), several blocks a sequence
and several loop steps a block; what the custom_vjp keeps, what the kernels
are named and write into the runtime's ring, and where ``auto`` takes them."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import conv, gated_short_conv
from ray_tpu.ops.remat import remat_policy
from tests.conftest import kernel_calls


def loop_reference(bcx, taps, dy):
    """y, dbcx, dtaps one position at a time, each sequence on its own, in
    float64: the equations of the module's docstring and their transposes."""
    bcx, taps, dy = (np.asarray(t, np.float64) for t in (bcx, taps, dy))
    batch, length, wide = bcx.shape
    h, k = wide // 3, taps.shape[0]
    b, c, x = bcx[..., :h], bcx[..., h:2 * h], bcx[..., 2 * h:]
    z = b * x
    y, dbcx, dtaps = np.zeros((batch, length, h)), np.zeros_like(bcx), \
        np.zeros_like(taps)
    for n in range(batch):
        dz = np.zeros((length, h))
        for t in range(length):
            conv_t = np.zeros(h)
            for j in range(k):
                src = t - (k - 1) + j
                if src >= 0:
                    conv_t += taps[j] * z[n, src]
            y[n, t] = c[n, t] * conv_t
            dbcx[n, t, h:2 * h] = dy[n, t] * conv_t
            dc = dy[n, t] * c[n, t]
            for j in range(k):
                src = t - (k - 1) + j
                if src >= 0:
                    dz[src] += taps[j] * dc
                    dtaps[j] += dc * z[n, src]
        dbcx[n, :, :h] = dz * x[n]
        dbcx[n, :, 2 * h:] = dz * b[n]
    return y, dbcx, dtaps


def _operands(batch, length, channels, dtype=jnp.float32, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(keys[0], (batch, length, 3 * channels),
                              jnp.float32).astype(dtype),
            jax.random.normal(keys[1], (conv.TAPS, channels), jnp.float32),
            jax.random.normal(keys[2], (batch, length, channels),
                              jnp.float32).astype(dtype))


def _out_and_grads(impl, bcx, taps, dy):
    y, pull = jax.vjp(lambda a, w: gated_short_conv(a, w, impl=impl), bcx,
                      taps)
    return (y, *pull(dy))


# (batch, length, channels, bytes of a block's chunk): one block of one loop
# step; one block of several steps; several blocks of one step (the rows
# before and after a block come from its neighbours); several of several;
# channels that are no whole slab of 256 lanes
_CASES = [(2, 32, 128, 2**20), (3, 128, 128, 2**20), (2, 96, 128, 2**14),
          (2, 256, 256, 2**16), (2, 64, 384, 2**20)]


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
@pytest.mark.parametrize("case", _CASES, ids=lambda c: "x".join(map(str, c)))
def test_conv_matches_the_loop(case, impl, monkeypatch):
    batch, length, channels, block_bytes = case
    monkeypatch.setattr(conv, "_BLOCK_BYTES", block_bytes)
    jax.clear_caches()
    bcx, taps, dy = _operands(batch, length, channels, seed=length)
    if impl != "jnp":
        rows = conv.block_rows(length, channels, 4)
        assert rows and rows * channels * 4 <= max(block_bytes,
                                                   32 * channels * 4)
    got = _out_and_grads(impl, bcx, taps, dy)
    want = loop_reference(bcx, taps, dy)
    for name, a, b in zip(("y", "dbcx", "dtaps"), got, want):
        np.testing.assert_allclose(np.asarray(a, np.float64), b, rtol=2e-5,
                                   atol=2e-5 * np.abs(b).max(), err_msg=name)
    jax.clear_caches()


def test_nothing_crosses_a_sequences_start():
    """Two sequences a batch against each of them alone: to the bit, by
    either form. A kernel that fetched the rows before a block across the
    batch's rows would fail the second sequence's first two positions."""
    bcx, taps, dy = _operands(2, 64, 128, seed=3)
    for impl in ("jnp", "pallas_interpret"):
        together = _out_and_grads(impl, bcx, taps, dy)
        alone = [_out_and_grads(impl, bcx[n:n + 1], taps, dy[n:n + 1])
                 for n in range(2)]
        for n in range(2):
            np.testing.assert_array_equal(together[0][n], alone[n][0][0])
            np.testing.assert_array_equal(together[1][n], alone[n][1][0])
        np.testing.assert_allclose(together[2], alone[0][2] + alone[1][2],
                                   rtol=1e-6, atol=1e-5)


@pytest.mark.parametrize("impl", ["jnp", "pallas_interpret"])
def test_bfloat16_operands_round_z_and_sum_in_float32(impl):
    """In bfloat16 ``z = B x`` is rounded to bfloat16, as the product of two
    such arrays is; the taps' sum and ``dtaps`` are float32: held against
    the loop on operands whose ``z`` was rounded by hand."""
    bcx, taps, dy = _operands(2, 64, 128, jnp.bfloat16, seed=5)
    y, dbcx, dtaps = _out_and_grads(impl, bcx, taps, dy)
    assert y.dtype == dbcx.dtype == jnp.bfloat16 and dtaps.dtype == jnp.float32
    f = np.asarray(bcx, np.float64)
    z = np.asarray((bcx[..., :128] * bcx[..., 256:]).astype(jnp.bfloat16),
                   np.float64)
    # the loop on (1, C, z): its y, dC and dtaps are the rounded-z ones
    rounded = np.concatenate([np.ones_like(z), f[..., 128:256], z], axis=-1)
    want_y, want_d, want_taps = loop_reference(rounded, taps, dy)
    scale = lambda t: np.abs(t).max()
    np.testing.assert_allclose(np.asarray(y, np.float64), want_y,
                               atol=2 ** -8 * scale(want_y), rtol=2 ** -7)
    np.testing.assert_allclose(np.asarray(dbcx[..., 128:256], np.float64),
                               want_d[..., 128:256],
                               atol=2 ** -8 * scale(want_d), rtol=2 ** -7)
    np.testing.assert_allclose(dtaps, want_taps, rtol=1e-5,
                               atol=1e-5 * scale(want_taps))


def test_the_kernels_match_the_jnp_form_in_bfloat16():
    """The two forms do the same arithmetic in another order: a rounding
    apart in bfloat16 outputs."""
    xs = _operands(2, 128, 256, jnp.bfloat16, seed=7)
    for a, b in zip(_out_and_grads("pallas_interpret", *xs),
                    _out_and_grads("jnp", *xs)):
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        assert np.abs(a - b).max() <= 2 ** -7 * np.abs(b).max()


def test_the_residuals_are_the_two_arguments():
    """Nothing the size of ``y`` is kept for the backward pass: the
    custom_vjp's residuals are ``bcx`` and the taps."""
    from jax._src.ad_checkpoint import saved_residuals

    bcx, taps, _ = _operands(2, 64, 128)
    for impl in ("jnp", "pallas_interpret"):
        kept = saved_residuals(
            lambda a, w: gated_short_conv(a, w, impl=impl).sum(), bcx, taps)
        assert sorted(aval.shape for aval, _ in kept) == sorted(
            [bcx.shape, taps.shape]), kept


def test_kernels_are_named_and_recorded():
    """The two ``pallas_call``s carry the names the benchmark's readers find
    them by, and each traced pass writes one ``conv/short`` record."""
    from ray_tpu._private import steptrace

    bcx = jax.ShapeDtypeStruct((4, 8192, 3 * 2048), jnp.bfloat16)
    taps = jax.ShapeDtypeStruct((3, 2048), jnp.float32)
    grad = jax.grad(lambda a, w: gated_short_conv(a, w, impl="pallas").astype(
        jnp.float32).sum(), argnums=(0, 1))
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        jax.clear_caches()  # the record is written where a call is traced
        jaxpr = jax.make_jaxpr(grad)(bcx, taps)
        records = [r["values"] for r in steptrace.snapshot()
                   if r["kind"] == "counters" and r["name"] == "conv/short"]
    finally:
        steptrace.set_enabled(False)
        jax.clear_caches()
    assert kernel_calls(jaxpr) == {"short_conv_fwd": 1, "short_conv_bwd": 1}
    assert {r["backward"] for r in records} == {0, 1}
    cells = 4 * 8192 * 2048 * 2
    for r in records:
        assert r == {"channels": 2048, "taps": 3, "tokens": 4 * 8192,
                     "sequences": 4, "backward": r["backward"],
                     "bytes_needed": (7 * cells + 2 * 3 * 2048 * 4
                                      if r["backward"]
                                      else 4 * cells + 3 * 2048 * 4)}
    assert conv.block_rows(8192, 2048, 2) == 256


def test_recomputation_runs_the_forward_kernel_again():
    """Under ``ops.remat.remat_policy`` a recomputed layer makes the
    convolution's output again (nothing of it is named for the policy): two
    forward kernels and one backward in the gradient."""
    bcx, taps, _ = _operands(1, 32, 128)

    def layer(a, w):
        return jnp.tanh(gated_short_conv(a * 2.0, w,
                                         impl="pallas_interpret")).sum()

    fn = jax.checkpoint(layer, policy=remat_policy())
    jaxpr = jax.make_jaxpr(jax.grad(fn, argnums=(0, 1)))(bcx, taps)
    assert kernel_calls(jaxpr) == {"short_conv_fwd": 2, "short_conv_bwd": 1}


def test_auto_takes_the_kernels_on_a_tpu_where_the_layout_fits(monkeypatch):
    bcx, taps = jnp.zeros((2, 64, 3 * 256)), jnp.zeros((3, 256))
    assert conv.auto_impl(bcx, taps) == "jnp"       # this process: a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert conv.auto_impl(bcx, taps) == "pallas"
    # a length that is no whole number of loop steps, channels off the
    # lanes, another number of taps: the jnp form's
    assert conv.auto_impl(jnp.zeros((2, 40, 3 * 256)), taps) == "jnp"
    assert conv.auto_impl(jnp.zeros((2, 64, 3 * 200)),
                          jnp.zeros((3, 200))) == "jnp"
    assert conv.auto_impl(bcx, jnp.zeros((4, 256))) == "jnp"
    assert conv.block_rows(40, 256, 4) == 0


@pytest.mark.parametrize("length", [40, 7])
def test_a_length_off_the_block_runs_the_jnp_form(length, monkeypatch):
    """``impl=None`` on a TPU for a length ``_ROWS`` does not divide: no
    kernel in the program, and the loop's values."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.clear_caches()
    bcx, taps, dy = _operands(2, length, 128, seed=length)
    fn = lambda a, w: gated_short_conv(a, w)
    assert not kernel_calls(jax.make_jaxpr(jax.grad(
        lambda a, w: fn(a, w).sum(), argnums=(0, 1)))(bcx, taps))
    y, pull = jax.vjp(fn, bcx, taps)
    want = loop_reference(bcx, taps, dy)
    for a, b in zip((y, *pull(dy)), want):
        np.testing.assert_allclose(np.asarray(a, np.float64), b, rtol=2e-5,
                                   atol=2e-5 * np.abs(b).max())
    jax.clear_caches()


def test_four_taps_run_the_jnp_form():
    """The ``jnp`` form takes any number of taps (the kernels are written
    for three)."""
    bcx, _, dy = _operands(1, 16, 128)
    taps = jax.random.normal(jax.random.PRNGKey(9), (4, 128))
    got = _out_and_grads("jnp", bcx, taps, dy)
    for a, b in zip(got, loop_reference(bcx, taps, dy)):
        np.testing.assert_allclose(np.asarray(a, np.float64), b, rtol=2e-5,
                                   atol=2e-5 * np.abs(b).max())

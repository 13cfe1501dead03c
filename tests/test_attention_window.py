"""Grouped key-value heads and a window in the attention ops (PR 44): the
naive reference against a mask written out by hand, the Pallas kernel in
interpret mode and the scan against the reference, the grid's blocks by
kind, and that a call with neither lowers to what it lowered to before."""

import functools
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention
from ray_tpu.ops.attention import (attention_reference, causal_self_attention,
                                   flash_attention, grid_block_kinds)
from tests.conftest import kernel_calls, kernel_whiles


def _operands(heads, kv_heads, length, d, d_v, dtype=jnp.float32, seed=7):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (heads, length, d), dtype),
            jax.random.normal(ks[1], (kv_heads, length, d), dtype),
            jax.random.normal(ks[2], (kv_heads, length, d_v), dtype),
            jax.random.normal(ks[3], (heads, length, d_v), jnp.float32))


def test_reference_is_the_mask_written_out():
    """Query head j reads key-value head j // group; query i sees keys j
    with 0 <= i - j < window, its own position among them."""
    q, k, v, _ = _operands(6, 2, 12, 4, 3)
    window, group = 5, 3
    want = np.zeros((6, 12, 3), np.float32)
    for h in range(6):
        for i in range(12):
            seen = [j for j in range(12) if 0 <= i - j < window]
            s = np.array([float(q[h, i] @ k[h // group, j]) for j in seen]) / 2
            p = np.exp(s - s.max())
            want[h, i] = (p / p.sum()) @ np.asarray(v[h // group])[seen]
    got = attention_reference(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(got, want, atol=1e-5, rtol=1e-5)
    assert len([j for j in range(12) if 0 <= 11 - j < window]) == window
    # a window that holds every key is the causal mask
    np.testing.assert_array_equal(
        attention_reference(q, k, v, causal=True, window=12),
        attention_reference(q, k, v, causal=True))
    with pytest.raises(AssertionError, match="causal"):
        attention_reference(q, k, v, window=4)


# (query heads, key-value heads, length, key width, value width, block_q,
# block_k, resident, window): ``resident`` overrides ``_MAX_RESIDENT`` so
# that a short sequence spans several grid blocks, as 16,384 tokens do on
# the chip. A window of whole blocks is walked by kind (diagonal, trailing,
# whole between them, dead); any other in loops.
_CASES = {
    "grouped_one_block": (8, 2, 64, 8, 8, 16, 16, None, None),
    "grouped_blocks_4x4": (8, 2, 128, 8, 8, 16, 16, 32, None),
    "window_in_one_block": (2, 2, 64, 8, 8, 16, 16, None, 24),
    "window_of_one_block": (2, 2, 128, 8, 8, 16, 16, 32, 32),
    "window_of_two_blocks_grouped": (8, 2, 128, 8, 8, 16, 16, 32, 64),
    "window_looped_grouped": (8, 2, 128, 8, 8, 16, 16, 32, 40),
    "window_narrower_than_a_tile": (4, 2, 128, 8, 8, 16, 16, 32, 5),
    "window_wide_keys_tiles_2x1": (4, 1, 128, 24, 16, 16, 32, 32, 32),
    "window_one_tile_a_block": (4, 1, 128, 24, 16, 32, 32, 32, 32),
    # differential attention's calls (PR 48): values twice as wide as the
    # keys, pairs of query heads on one pair of key-value heads; and its
    # window layer's, a quarter of a grid block wide (512 keys of 2,048)
    "values_twice_the_keys_one_block": (4, 2, 64, 8, 16, 16, 16, None, None),
    "values_twice_the_keys_blocks_4x4": (4, 2, 128, 8, 16, 16, 16, 32, None),
    "window_a_quarter_of_a_block": (4, 2, 128, 8, 16, 16, 16, 32, 8),
    "window_a_quarter_of_a_block_tiles_2x2": (4, 2, 128, 8, 16, 16, 16, 64,
                                              16),
}
_BF16 = ("grouped_blocks_4x4", "window_of_one_block",
         "window_of_two_blocks_grouped", "window_wide_keys_tiles_2x1",
         "values_twice_the_keys_blocks_4x4", "window_a_quarter_of_a_block")
_PARAMS = [
    pytest.param(case, impl, dtype, id=f"{case}-{impl}-{dtype.__name__}")
    for dtype, impls, cases in (
        (jnp.float32, ("pallas_interpret", "scan"), _CASES),
        (jnp.bfloat16, ("pallas_interpret",), _BF16))
    for case in cases for impl in impls]


@pytest.mark.parametrize("case,impl,dtype", _PARAMS)
def test_window_and_grouped_heads_match_reference(monkeypatch, case, impl,
                                                  dtype):
    """Forward and the gradients of q, k and v under a non-uniform
    cotangent: float32 entry by entry, bfloat16 against the largest
    reference entry (``tests/test_ops.py``'s limits). dK and dV of a
    key-value head are sums over its group of query heads."""
    heads, kv, length, d, d_v, bq, bk, resident, window = _CASES[case]
    if resident:
        monkeypatch.setattr(attention, "_MAX_RESIDENT", resident)
        jax.clear_caches()  # flash_attention is jitted: the rule is read
    q, k, v, w = _operands(heads, kv, length, d, d_v, dtype)
    f32 = lambda x: x.astype(jnp.float32)

    def out_and_grads(fn, *args):
        out, vjp = jax.vjp(fn, *args)
        return out, vjp(w)

    flash = lambda q, k, v: f32(flash_attention(
        q, k, v, causal=True, window=window, impl=impl,
        block_q=bq if impl != "scan" else None, block_k=bk))
    ref = lambda q, k, v: attention_reference(q, k, v, causal=True,
                                              window=window)
    fwd_tol, grad_tol = ((2e-5, 1e-4) if dtype == jnp.float32
                         else (2e-2, 3e-2))

    def close(got, want, tol):
        if dtype == jnp.float32:
            np.testing.assert_allclose(got, want, atol=tol, rtol=tol)
        else:
            assert float(jnp.abs(f32(got) - want).max()
                         / jnp.abs(want).max()) <= tol

    try:
        out, grads = jax.jit(functools.partial(out_and_grads, flash))(q, k, v)
        want, want_grads = jax.jit(functools.partial(out_and_grads, ref))(
            f32(q), f32(k), f32(v))
    finally:
        if resident:
            jax.clear_caches()
    close(out, want, fwd_tol)
    for got, wanted, like in zip(grads, want_grads, (q, k, v)):
        assert got.shape == like.shape and got.dtype == dtype
        close(got, wanted, grad_tol)


def test_grid_blocks_by_kind_under_a_window():
    kinds = lambda w, d, t, x, l=0: {"whole": w, "diagonal": d,
                                     "trailing": t, "dead": x, "looped": l}
    for backward in (False, True):
        # Trinity-Mini's window layer at 16,384: the window is one block
        assert grid_block_kinds(16384, 16384, True, backward=backward,
                                window=2048) == kinds(0, 8, 7, 49)
        assert grid_block_kinds(8192, 8192, True, backward=backward,
                                window=4096) == kinds(3, 4, 2, 7)
    # without a window what it gave, "trailing" not among the keys
    assert grid_block_kinds(16384, 16384, True) == {
        "whole": 28, "diagonal": 8, "dead": 28, "looped": 0}
    assert grid_block_kinds(8192, 8192, True) == {
        "whole": 6, "diagonal": 4, "dead": 6, "looped": 0}
    # a window that holds every key is none
    assert grid_block_kinds(2048, 2048, True, window=2048) == {
        "whole": 0, "diagonal": 1, "dead": 0, "looped": 0}
    # one block a head is one kind whatever the window
    assert grid_block_kinds(2048, 2048, True, window=512) == kinds(0, 1, 0, 0)
    # a window that is no whole number of blocks keeps the loops
    assert grid_block_kinds(4096, 4096, True, window=1024) == kinds(
        0, 0, 0, 0, 4)
    pairs = 2048 * 16384 - 2048 * 2047 // 2
    assert pairs == 31_458_304 and 16384 * 16385 // 2 == 134_225_920
    # Phi-4-mini-flash's window layer at 16,384 (PR 48): 512 keys are a
    # quarter of a block, so every block of the 8 x 8 is walked in loops
    for backward in (False, True):
        assert grid_block_kinds(16384, 16384, True, backward=backward,
                                window=512) == kinds(0, 0, 0, 0, 64)


def test_auto_takes_the_kernel_at_keys_64_and_values_128(monkeypatch):
    """A differential layer's maps (PR 48): keys 64 wide, the pair's values
    128. On a TPU ``auto`` is the kernel there, as at the widths measured
    before; a width nobody measured stays with XLA."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    q = jnp.zeros((1, 16384, 20, 64), jnp.bfloat16)
    wide = lambda d: jnp.zeros((1, 16384, 10, d), jnp.bfloat16)
    assert attention.auto_attention(q, wide(128)) == "flash"
    assert attention.auto_attention(q, wide(64)) == "flash"
    assert attention.auto_attention(q, wide(256)) == "xla"
    assert (64, 128) in attention._FLASH_HEAD_DIMS


def test_a_windowed_grouped_call_is_named_and_recorded():
    """The kernels of a windowed call carry the window in their names (the
    benchmark's readers find it there), their grid's blocks by kind, the
    window and both head counts go into the runtime's ring, one record a
    traced call, and neither kernel holds a loop with a traced bound."""
    from ray_tpu._private import steptrace

    q = jax.ShapeDtypeStruct((32, 16384, 128), jnp.bfloat16)
    k = jax.ShapeDtypeStruct((4, 16384, 128), jnp.bfloat16)
    grad = jax.grad(lambda *x: flash_attention(
        *x, causal=True, window=2048, impl="pallas").astype(
            jnp.float32).sum(), argnums=(0, 1, 2))
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        jax.clear_caches()  # the record is written where a call is traced
        jaxpr = jax.make_jaxpr(grad)(q, k, k)
        records = [r["values"] for r in steptrace.snapshot()
                   if r["kind"] == "counters"
                   and r["name"] == "attn/grid_blocks"]
    finally:
        steptrace.set_enabled(False)
        jax.clear_caches()
    assert kernel_calls(jaxpr) == {"flash_fwd_w2048": 1, "flash_bwd_w2048": 1}
    assert not kernel_whiles(jaxpr)
    assert {r["backward"] for r in records} == {0, 1}
    for r in records:
        assert r == {"whole": 0, "diagonal": 8, "trailing": 7, "dead": 49,
                     "looped": 0, "queries": 16384, "keys": 16384,
                     "backward": r["backward"], "window": 2048, "heads": 32,
                     "kv_heads": 4}
    # dQ's float32 partials: two a block of queries, not one a block of keys
    written = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                written.extend(v.aval.shape for v in eqn.outvars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert (2, 32, 128, 16384) in written
    assert (8, 32, 128, 16384) not in written


def test_a_window_over_lengths_that_differ_is_refused():
    q, k, v, _ = _operands(2, 2, 64, 8, 8)
    with pytest.raises(NotImplementedError, match="window"):
        flash_attention(q[:, :32], k, v, causal=True, window=16,
                        impl="pallas_interpret")
    # the scan and the reference take it
    np.testing.assert_allclose(
        flash_attention(q[:, :32], k, v, causal=True, window=16, impl="scan",
                        block_k=16),
        attention_reference(q[:, :32], k, v, causal=True, window=16),
        atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("path", ["xla", "flash"])
def test_causal_self_attention_takes_both_in_a_models_layout(monkeypatch,
                                                             path):
    """[B, T, H, d] against [B, T, H_kv, d] with a batch of two: the folded
    batch x heads keeps query head j on key-value head j // group."""
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (2, 64, 4, 8))
    k = jax.random.normal(ks[1], (2, 64, 2, 8))
    v = jax.random.normal(ks[2], (2, 64, 2, 8))
    if path == "flash":   # the kernel, where there is no chip: interpreted
        monkeypatch.setattr(
            attention, "flash_attention", functools.partial(
                flash_attention, impl="pallas_interpret", block_q=16,
                block_k=16))
    bhsd = lambda t: t.transpose(0, 2, 1, 3)
    for window in (None, 24):
        want = jnp.stack([
            attention_reference(bhsd(q)[b], bhsd(k)[b], bhsd(v)[b],
                                causal=True, window=window)
            for b in range(2)]).transpose(0, 2, 1, 3)
        got = causal_self_attention(q, k, v, path, window)
        np.testing.assert_allclose(got, want, atol=2e-5, rtol=2e-5)


# sha256 (first 16 digits) of the jaxpr's text at the parent of PR 44
# (commit b403d28), forward and gradient: a call without a window and with
# as many key-value heads lowers to what the parent lowered, the kernels'
# names included. (q, k, v shapes, causal.)
_PARENT = {
    "gpt2_1024_64": (((192, 1024, 64),) * 3, True,
                     "5f3323b1f1ffc1ef", "3890c4a154dfc8c0"),
    "several_blocks_4096_64": (((48, 4096, 64),) * 3, True,
                               "0e0c8415dffe7b78", "321e8b47cb4677fb"),
    "latent_8192_192_128": (((64, 8192, 192), (64, 8192, 192),
                             (64, 8192, 128)), True,
                            "1c74b3b506b9de82", "a9ecd4230e6d3d94"),
    "cross_4096_8192_looped": (((8, 4096, 128), (8, 8192, 128),
                                (8, 8192, 128)), True,
                               "3492ea75658517fb", "8237c180cb040deb"),
    "no_mask_4096": (((8, 4096, 128),) * 3, False,
                     "47ba714f8f7f46f9", "9d0553e84f35987f"),
    "batch_and_heads_1024": (((2, 12, 1024, 64),) * 3, True,
                             "6118297c0a2f298f", "508cd278a20b9913"),
}


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("case", _PARENT)
def test_a_call_without_either_lowers_to_the_parents_jaxpr(case, backward):
    shapes, causal, fwd, grad = _PARENT[case]
    q, k, v = (jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes)
    fn = lambda q, k, v: flash_attention(q, k, v, causal=causal,
                                         impl="pallas")
    if backward:
        fn = jax.grad(lambda *x, fn=fn: fn(*x).astype(jnp.float32).sum(),
                      argnums=(0, 1, 2))
    text = str(jax.make_jaxpr(fn)(q, k, v))
    assert hashlib.sha256(text.encode()).hexdigest()[:16] == (
        grad if backward else fwd)

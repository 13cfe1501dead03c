"""Grouped key-value heads and a window in the attention ops (PR 44): the
naive reference against a mask written out by hand, the Pallas kernel in
interpret mode and the scan against the reference, the grid's blocks by
kind, and that a call with neither lowers to the jaxpr held here; since PR
50 the backward's dQ sum over several blocks of keys, which the kernel makes
itself: one array a call, in copies of whole rows of tiles; since PR 51 the
boundary of a one-block call, the model's own [B, T, H x d] arrays or the
[B x H, T, d] that XLA makes of them; since PR 55 the boundary of a call over
several blocks of keys at one width of whole lane tiles, whose kernels write
O, dK and dV into, and read O and its cotangent from, the model's arrays;
since PR 66 the grid of a windowed call whose blocks' kinds are told apart,
which holds a row's live blocks alone."""

import functools
import hashlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention, flash_kernels
from ray_tpu.ops.attention import (attention_reference, causal_self_attention,
                                   flash_attention)
from ray_tpu.ops.flash_kernels import grid_block_kinds
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
    # the backward's dQ sum over several blocks of keys (PR 50), at the
    # three cells' groups and key widths: no window (a block of queries is
    # assigned at block 0 and added to up to its diagonal), a window of
    # whole blocks (assigned at its trailing block), one that is none
    # (looped: assigned at the first block the window leaves it)
    "dq_sum_group_1_keys_192": (2, 2, 128, 192, 128, 16, 16, 32, None),
    "dq_sum_group_2_keys_64": (4, 2, 128, 64, 128, 16, 16, 32, None),
    "dq_sum_group_8_keys_128": (8, 1, 128, 128, 128, 16, 16, 32, None),
    "dq_sum_group_1_keys_192_window_of_two_blocks": (2, 2, 128, 192, 128, 16,
                                                     16, 32, 64),
    "dq_sum_group_2_keys_64_window_of_one_block": (4, 2, 128, 64, 128, 16,
                                                   16, 32, 32),
    "dq_sum_group_8_keys_128_window_of_one_block": (8, 1, 128, 128, 128, 16,
                                                    16, 32, 32),
    "dq_sum_group_1_keys_192_window_looped": (2, 2, 128, 192, 128, 16, 16,
                                              32, 40),
    "dq_sum_group_2_keys_64_window_looped": (4, 2, 128, 64, 128, 16, 16, 32,
                                             8),
    "dq_sum_group_8_keys_128_window_looped": (8, 1, 128, 128, 128, 16, 32,
                                              32, 24),
    # a window of half a grid block that takes residents of its own length
    # (PR 62: ``_WINDOW_RESIDENT_FROM``, here half of ``resident``): 8 x 8
    # grid blocks a head, walked by kind, at Mellum's group of 8
    "window_half_a_block_takes_its_own_residents": (8, 2, 128, 8, 8, 8, 8,
                                                    32, 16),
    "dq_sum_group_8_keys_128_window_half_a_block": (8, 1, 128, 128, 128, 16,
                                                    16, 32, 16),
    # no group and a window of one block over 8 blocks of residents (PR 66):
    # a block of queries' dQ^T sum is written on the last step of one block
    # of keys and fetched on the first step of the next
    "dq_sum_group_1_keys_128_window_of_one_block_of_8": (2, 2, 256, 128, 128,
                                                         16, 16, 32, 32),
}
# (blocks of residents a head, blocks a row's window leaves anything of) of
# the cases whose windowed calls launch a row's live blocks alone (PR 66:
# ``flash_kernels._live_span``); every other case keeps the grid of all
# ``nq x nk`` blocks a head
_SPANS = {
    "window_of_one_block": (4, 2),
    "window_of_two_blocks_grouped": (4, 3),
    "window_wide_keys_tiles_2x1": (4, 2),
    "window_one_tile_a_block": (4, 2),
    "dq_sum_group_1_keys_192_window_of_two_blocks": (4, 3),
    "dq_sum_group_2_keys_64_window_of_one_block": (4, 2),
    "dq_sum_group_8_keys_128_window_of_one_block": (4, 2),
    "window_half_a_block_takes_its_own_residents": (8, 2),
    "dq_sum_group_8_keys_128_window_half_a_block": (8, 2),
    "dq_sum_group_1_keys_128_window_of_one_block_of_8": (8, 2),
}
_BF16 = ("grouped_blocks_4x4", "window_of_one_block",
         "window_half_a_block_takes_its_own_residents",
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
        monkeypatch.setattr(flash_kernels, "_MAX_RESIDENT", resident)
        monkeypatch.setattr(flash_kernels, "_WINDOW_RESIDENT_FROM",
                            resident // 2)
        jax.clear_caches()  # flash_attention is jitted: the rule is read
        if "half_a_block" in case:
            assert grid_block_kinds(length, length, True, bq, bk,
                                    window=window) == {
                "whole": 0, "diagonal": 8, "trailing": 7, "dead": 49,
                "looped": 0, "steps": 16, "dead_steps": 1}
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
        if impl != "scan" and resident:
            grids = _kernel_grids(jax.make_jaxpr(
                functools.partial(out_and_grads, flash))(q, k, v))
    finally:
        if resident:
            jax.clear_caches()
    if impl != "scan" and resident:
        # the grid a head: every block of it, or under a window whose
        # blocks' kinds are told apart a row's live ones (PR 66)
        nq, span = _SPANS.get(case, (length // resident, None))
        name = lambda base: base if window is None else f"{base}_w{window}"
        assert grids[name("flash_fwd")] == (heads, nq, span or nq)
        assert grids[name("flash_bwd")] == (kv, nq,
                                            heads // kv * (span or nq))
    close(out, want, fwd_tol)
    for got, wanted, like in zip(grads, want_grads, (q, k, v)):
        assert got.shape == like.shape and got.dtype == dtype
        close(got, wanted, grad_tol)


# (query heads, key-value heads, key width, window, rows of tiles a copy):
# 256 tokens in 4 x 4 grid blocks of 4 rows of tiles each
_PIECES = {
    "a_row_a_copy_group_1": (2, 2, 192, None, 1),
    "two_rows_a_copy_group_8": (8, 1, 128, None, 2),
    "two_rows_a_copy_group_2_window_of_a_block": (4, 2, 64, 64, 2),
    "a_row_a_copy_group_2_window_looped": (4, 2, 64, 40, 1),
    "two_rows_a_copy_group_1_window_of_two_blocks": (2, 2, 192, 128, 2),
    "the_block_a_copy_group_8_window_looped": (8, 1, 128, 24, 4),
}


@pytest.mark.parametrize("case", _PIECES)
def test_the_dq_sum_moves_in_pieces(monkeypatch, case):
    """The backward's float32 dQ^T sum goes to HBM and back in copies of
    whole rows of tiles up to ``_COPY_BYTES`` (PR 50): a row, two or the
    whole block of queries a copy, rows walked up (whole and trailing
    blocks, loops) and down (diagonal ones), give dQ, dK and dV as the
    reference does."""
    heads, kv, d, window, rows = _PIECES[case]
    monkeypatch.setattr(flash_kernels, "_MAX_RESIDENT", 64)
    monkeypatch.setattr(flash_kernels, "_COPY_BYTES", 4 * d * 16 * rows)
    jax.clear_caches()
    q, k, v, w = _operands(heads, kv, 256, d, 128)
    flash = lambda q, k, v: flash_attention(
        q, k, v, causal=True, window=window, impl="pallas_interpret",
        block_q=16, block_k=16)
    ref = lambda q, k, v: attention_reference(q, k, v, causal=True,
                                              window=window)
    try:
        jaxpr = jax.make_jaxpr(lambda *x: jax.vjp(flash, *x)[1](w))(q, k, v)
        grads = jax.jit(lambda *x: jax.vjp(flash, *x)[1](w))(q, k, v)
        want = jax.jit(lambda *x: jax.vjp(ref, *x)[1](w))(q, k, v)
    finally:
        jax.clear_caches()
    # the sum's two buffers in VMEM: pieces x d x (rows x 16 queries)
    name = "flash_bwd" if window is None else f"flash_bwd_w{window}"
    assert _kernel_scratch(jaxpr)[name][2] == (4 // rows, d, 16 * rows)
    for got, wanted in zip(grads, want):
        np.testing.assert_allclose(got, wanted, atol=1e-4, rtol=1e-4)


def test_grid_blocks_by_kind_under_a_window():
    """The kinds are the mask's geometry over a head's ``nq x nk`` blocks;
    ``steps`` and ``dead_steps`` what the call's grid launches of them
    (PR 66): every block, or under a window whose blocks' kinds are told
    apart a row's live ones, the only dead steps left those before a
    head's first block of keys (after its last block of queries)."""
    def kinds(w, d, t, x, l=0, steps=None, dead_steps=None):
        total = w + d + t + x + l
        return {"whole": w, "diagonal": d, "trailing": t, "dead": x,
                "looped": l, "steps": total if steps is None else steps,
                "dead_steps": x if dead_steps is None else dead_steps}

    for backward in (False, True):
        # Trinity-Mini's window layer at 16,384: the window is one block,
        # a row its trailing and its diagonal block
        assert grid_block_kinds(16384, 16384, True, backward=backward,
                                window=2048) == kinds(0, 8, 7, 49, 0, 16, 1)
        # two blocks of window: three steps a row, 1 + 2 past the edge
        assert grid_block_kinds(8192, 8192, True, backward=backward,
                                window=4096) == kinds(3, 4, 2, 7, 0, 12, 3)
    # without a window what it gave, "trailing" not among the keys, and
    # every block a step
    assert grid_block_kinds(16384, 16384, True) == {
        "whole": 28, "diagonal": 8, "dead": 28, "looped": 0, "steps": 64,
        "dead_steps": 28}
    assert grid_block_kinds(8192, 8192, True) == {
        "whole": 6, "diagonal": 4, "dead": 6, "looped": 0, "steps": 16,
        "dead_steps": 6}
    # a window that holds every key is none
    assert grid_block_kinds(2048, 2048, True, window=2048) == {
        "whole": 0, "diagonal": 1, "dead": 0, "looped": 0, "steps": 1,
        "dead_steps": 0}
    # one block a head is one kind whatever the window
    assert grid_block_kinds(2048, 2048, True, window=512) == kinds(0, 1, 0, 0)
    # a window that is no whole number of blocks keeps the loops
    assert grid_block_kinds(4096, 4096, True, window=1536) == kinds(
        0, 0, 0, 0, 4)
    for backward in (False, True):
        # from 1,024 keys up a window of whole tiles that divides the
        # lengths takes residents of its own length (PR 62): Mellum's
        # window layer at 8,192 is 8 x 8 blocks a head, none looped
        assert grid_block_kinds(8192, 8192, True, backward=backward,
                                window=1024) == kinds(0, 8, 7, 49, 0, 16, 1)
        assert grid_block_kinds(4096, 4096, True, backward=backward,
                                window=1024) == kinds(0, 4, 3, 9, 0, 8, 1)
        # phi-4-mini-flash's 512 keys stay a quarter of a block
        assert grid_block_kinds(16384, 16384, True, backward=backward,
                                window=512) == kinds(0, 0, 0, 0, 64)
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
                     "kv_heads": 4, "dq_partials": 0, "steps": 16,
                     "dead_steps": 1}
    # a row's trailing and diagonal block, a block of keys' own and next
    # block of queries for each of the group's eight heads (PR 66)
    assert _kernel_grids(jaxpr) == {"flash_fwd_w2048": (32, 8, 2),
                                    "flash_bwd_w2048": (4, 8, 8 * 2)}
    # dQ^T leaves the backward call as one float32 sum a query head
    assert _kernel_outputs(jaxpr)["flash_bwd_w2048"][0] == (
        (32, 128, 16384), jnp.float32)


def _kernel_eqns(jaxpr):
    """{kernel's name: its ``pallas_call`` equation} of ``jaxpr``."""
    found = {}

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                found[eqn.params["name"]] = eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return found


def _kernel_outputs(jaxpr):
    """{kernel's name: [(shape, dtype) of each output]}."""
    return {name: [(v.aval.shape, v.aval.dtype) for v in eqn.outvars]
            for name, eqn in _kernel_eqns(jaxpr).items()}


def _kernel_grids(jaxpr):
    """{kernel's name: its grid}."""
    return {name: eqn.params["grid_mapping"].grid
            for name, eqn in _kernel_eqns(jaxpr).items()}


def _kernel_scratch(jaxpr):
    """{kernel's name: [shape of each scratch operand]}."""
    return {name: [v.aval.shape for v in eqn.params["jaxpr"].invars[
        -eqn.params["grid_mapping"].num_scratch_operands:]]
        for name, eqn in _kernel_eqns(jaxpr).items()}


def _reductions(jaxpr):
    """Operand shapes of every reduction in ``jaxpr`` outside the kernels."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                continue
            if eqn.primitive.name.startswith("reduce"):
                found.extend(v.aval.shape for v in eqn.invars)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    return found


# (q, k, v shapes, window): the three cells' calls, and lengths that differ
_BACKWARD_CALLS = {
    "joyai_8k_keys_192": ((64, 8192, 192), (64, 8192, 192), (64, 8192, 128),
                          None),
    "trinity_16k_group_8": ((32, 16384, 128), (4, 16384, 128),
                            (4, 16384, 128), None),
    "trinity_16k_group_8_window_2048": ((32, 16384, 128), (4, 16384, 128),
                                        (4, 16384, 128), 2048),
    "phi4_16k_group_2_keys_64": ((20, 16384, 64), (10, 16384, 64),
                                 (10, 16384, 128), None),
    "phi4_16k_group_2_keys_64_window_512": ((20, 16384, 64), (10, 16384, 64),
                                            (10, 16384, 128), 512),
    "cross_4096_of_8192": ((8, 4096, 128), (8, 8192, 128), (8, 8192, 128),
                           None),
    "gpt2_one_block": ((192, 1024, 64),) * 3 + (None,),
}


@pytest.mark.parametrize("case", _BACKWARD_CALLS)
def test_the_backward_call_hands_back_one_dq(case):
    """Whatever the blocks of keys a head, the group, the widths and the
    window (PR 50): the backward call's first output is ONE dQ^T array
    [B x H, d, T], float32 where the kernel summed it over several blocks
    of keys and the operands' dtype at one; no output has a leading axis of
    partials, and XLA reduces nothing with one after it: what it reduces is
    ``delta``'s [B x H, T, d_v] alone."""
    *shapes, window = _BACKWARD_CALLS[case]
    q, k, v = (jax.ShapeDtypeStruct(s, jnp.bfloat16) for s in shapes)
    jaxpr = jax.make_jaxpr(jax.grad(lambda *x: flash_attention(
        *x, causal=True, window=window, impl="pallas").astype(
            jnp.float32).sum(), argnums=(0, 1, 2)))(q, k, v)
    b, t, d = q.shape
    name = "flash_bwd" if window is None else f"flash_bwd_w{window}"
    several = k.shape[1] > flash_kernels._MAX_RESIDENT
    dq, dk, dv = _kernel_outputs(jaxpr)[name]
    assert dq == ((b, d, t), jnp.float32 if several else jnp.bfloat16)
    assert dk == (k.shape, jnp.bfloat16) and dv == (v.shape, jnp.bfloat16)
    assert all(len(shape) == 3 for outs in _kernel_outputs(jaxpr).values()
               for shape, _ in outs)
    reduced = [s for s in _reductions(jaxpr) if len(s) >= 3]
    assert reduced and set(reduced) == {(b, t, v.shape[2])}, reduced


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


# (query heads, key-value heads, length, key width, value width, window,
# block_q, block_k, heads a lane tile): causal self-attention in a model's
# layout, batch 2. ``heads a lane tile`` is what ``heads_a_lane_tile`` must
# answer: 2 or 1 where the kernels address the model's [B, T, H x d] arrays
# (an odd number of 64-wide heads leaves half a tile past the arrays' edge),
# 0 where the call keeps the [B x H, T, d] boundary and its kernels.
_BOUNDARY = {
    "two_heads_64": (2, 2, 128, 64, 64, None, None, None, 2),
    "twelve_heads_64_tiles_2x4": (12, 12, 256, 64, 64, None, 128, 64, 2),
    "odd_heads_64": (3, 3, 128, 64, 64, None, 64, 64, 2),
    "odd_heads_64_window": (5, 5, 256, 64, 64, 100, 128, 128, 2),
    "two_heads_64_window_of_a_tile": (2, 2, 256, 64, 64, 64, 64, 64, 2),
    "two_heads_128": (2, 2, 128, 128, 128, None, 64, 32, 1),
    "three_heads_128_window": (3, 3, 256, 128, 128, 72, 128, 128, 1),
    "grouped_64": (4, 2, 128, 64, 64, None, 64, 64, 0),
    "grouped_128_window": (4, 2, 128, 128, 128, 48, 64, 64, 0),
    "values_twice_the_keys": (2, 2, 128, 64, 128, None, 64, 64, 0),
    "one_head_64": (1, 1, 128, 64, 64, None, 64, 64, 0),
    "no_whole_tile_of_queries": (2, 2, 64, 64, 64, None, 32, 32, 0),
    "width_32": (4, 4, 128, 32, 32, None, 64, 64, 0),
}
_BOUNDARY_PARAMS = [
    pytest.param(case, dtype, id=f"{case}-{dtype.__name__}")
    for dtype, cases in (
        (jnp.float32, _BOUNDARY),
        (jnp.bfloat16, ("twelve_heads_64_tiles_2x4", "odd_heads_64_window",
                        "two_heads_128", "grouped_64")))
    for case in cases]


@pytest.mark.parametrize("case,dtype", _BOUNDARY_PARAMS)
def test_a_one_block_call_by_either_boundary_matches_reference(
        monkeypatch, case, dtype):
    """``causal_self_attention(..., "flash")`` in interpret mode against
    the reference in float32, output and the three gradients under a
    non-uniform cotangent, and which arrays its kernels were handed: the
    model's own, a (T, 128) block a grid step, where the rule admits the
    call; else the parent's operands (V^T and K^T from XLA, ``delta`` a
    row a head)."""
    heads, kv, length, d, d_v, window, bq, bk, a_tile = _BOUNDARY[case]
    assert attention.heads_a_lane_tile(length, heads, kv, d, d_v) == a_tile
    monkeypatch.setattr(attention, "flash_attention", functools.partial(
        flash_attention, impl="pallas_interpret", block_q=bq, block_k=bk))
    ks = jax.random.split(jax.random.PRNGKey(11), 4)
    q = jax.random.normal(ks[0], (2, length, heads, d), dtype)
    k = jax.random.normal(ks[1], (2, length, kv, d), dtype)
    v = jax.random.normal(ks[2], (2, length, kv, d_v), dtype)
    w = jax.random.normal(ks[3], (2, length, heads, d_v), jnp.float32)
    f32 = lambda x: x.astype(jnp.float32)
    bhsd = lambda t: t.transpose(0, 2, 1, 3)
    flash = lambda *x: f32(causal_self_attention(*x, "flash", window))
    ref = lambda *x: bhsd(attention_reference(
        *map(bhsd, x), causal=True, window=window))

    def out_and_grads(fn, *args):
        out, vjp = jax.vjp(fn, *args)
        return (out, *vjp(w))

    got = out_and_grads(flash, q, k, v)
    want = out_and_grads(ref, f32(q), f32(k), f32(v))
    for a, b, like in zip(got, want, (w, q, k, v)):
        assert a.shape == like.shape
        if dtype == jnp.float32:
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
        else:
            assert float(jnp.abs(f32(a) - b).max() / jnp.abs(b).max()) <= 3e-2

    calls = _kernel_eqns(jax.make_jaxpr(
        lambda *x: out_and_grads(flash, *x))(q, k, v))
    shapes = {name: [v.aval.shape for v in eqn.invars]
              for name, eqn in calls.items()}
    name = lambda base: base if window is None else f"{base}_w{window}"
    if a_tile:
        own, tiles = (2, length, heads * d), -(-heads // a_tile)
        rows = (2, tiles, a_tile, length)
        assert shapes[name("flash_fwd")] == [own] * 3
        assert shapes[name("flash_bwd")] == [own] * 5 + [rows]
        assert calls[name("flash_bwd")].params["grid_mapping"].grid == (
            2, tiles)
    else:
        folded = lambda n, width: (2 * n, length, width)
        row = (2 * heads, 1, length)
        assert shapes[name("flash_fwd")] == [
            folded(heads, d), folded(kv, d), (2 * kv, d_v, length)]
        assert shapes[name("flash_bwd")] == [
            folded(heads, d), folded(kv, d), folded(kv, d_v),
            (2 * kv, d, length), folded(heads, d_v), row, row]


# (query heads, key-value heads, length, key width, value width, window,
# resident queries and keys, whether the results cross in the model's
# arrays): causal self-attention in a model's layout over SEVERAL blocks of
# keys a head (``_MAX_RESIDENT`` patched down to ``resident``; tiles of 16),
# batch 2. Admitted (PR 55): keys and values of one width of whole lane
# tiles, any grouping, a window or none; the others keep [B x H, T, d].
_RESULTS = {
    "group_8_full": (8, 1, 128, 128, 128, None, 32, True),
    "group_1_full": (2, 2, 128, 128, 128, None, 32, True),
    "group_8_window_of_a_block": (8, 1, 128, 128, 128, 32, 32, True),
    "group_1_window_of_a_block": (2, 2, 128, 128, 128, 32, 32, True),
    "group_2_window_of_two_blocks": (4, 2, 128, 128, 128, 64, 32, True),
    "group_8_window_of_part_blocks": (8, 1, 128, 128, 128, 40, 32, True),
    "group_1_window_of_part_blocks": (2, 2, 128, 128, 128, 24, 32, True),
    "group_2_two_blocks_a_head": (4, 2, 128, 128, 128, None, 64, True),
    "width_of_two_tiles": (2, 1, 64, 256, 256, None, 32, True),
    "keys_192_values_128": (2, 2, 128, 192, 128, None, 32, False),
    "keys_64_values_128_window": (4, 2, 128, 64, 128, 32, 32, False),
    "width_64": (4, 4, 128, 64, 64, None, 32, False),
}
_RESULTS_PARAMS = [
    pytest.param(case, dtype, id=f"{case}-{dtype.__name__}")
    for dtype, cases in (
        (jnp.float32, _RESULTS),
        (jnp.bfloat16, ("group_8_full", "group_8_window_of_a_block",
                        "group_1_window_of_part_blocks", "width_64")))
    for case in cases]


@pytest.mark.parametrize("case,dtype", _RESULTS_PARAMS)
def test_a_call_over_several_blocks_by_either_boundary_matches_reference(
        monkeypatch, case, dtype):
    """``causal_self_attention(..., "flash")`` in interpret mode against
    the reference in float32, output and the three gradients under a
    non-uniform cotangent, and which arrays its kernels handed over: where
    ``results_in_model_arrays`` admits the call, O, dK and dV are the
    model's own [B, T, H x width], dO and O go in as such arrays (the
    kernel makes ``delta``: nothing outside it reduces), dQ is the float32
    [B x H, d, T] sum it was and the first three operands the [B x H, T,
    d], [B x H_kv, T, d] and V^T that they were; else every operand and
    result is the parent's."""
    heads, kv, length, d, d_v, window, resident, results = _RESULTS[case]
    monkeypatch.setattr(flash_kernels, "_MAX_RESIDENT", resident)
    assert attention.results_in_model_arrays(length, d, d_v) == results
    assert not attention.heads_a_lane_tile(length, heads, kv, d, d_v)
    monkeypatch.setattr(attention, "flash_attention", functools.partial(
        flash_attention, impl="pallas_interpret", block_q=16, block_k=16))
    jax.clear_caches()  # flash_attention is jitted: the rule is read
    ks = jax.random.split(jax.random.PRNGKey(13), 4)
    q = jax.random.normal(ks[0], (2, length, heads, d), dtype)
    k = jax.random.normal(ks[1], (2, length, kv, d), dtype)
    v = jax.random.normal(ks[2], (2, length, kv, d_v), dtype)
    w = jax.random.normal(ks[3], (2, length, heads, d_v), jnp.float32)
    f32 = lambda x: x.astype(jnp.float32)
    bhsd = lambda t: t.transpose(0, 2, 1, 3)
    flash = lambda *x: f32(causal_self_attention(*x, "flash", window))
    ref = lambda *x: bhsd(attention_reference(
        *map(bhsd, x), causal=True, window=window))

    def out_and_grads(fn, *args):
        out, vjp = jax.vjp(fn, *args)
        return (out, *vjp(w))

    try:
        got = jax.jit(functools.partial(out_and_grads, flash))(q, k, v)
        jaxpr = jax.make_jaxpr(lambda *x: out_and_grads(flash, *x))(q, k, v)
    finally:
        jax.clear_caches()
    want = out_and_grads(ref, f32(q), f32(k), f32(v))
    for a, b, like in zip(got, want, (w, q, k, v)):
        assert a.shape == like.shape
        if dtype == jnp.float32:
            np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4)
        else:
            assert float(jnp.abs(f32(a) - b).max() / jnp.abs(b).max()) <= 3e-2

    name = lambda base: base if window is None else f"{base}_w{window}"
    calls = _kernel_eqns(jaxpr)
    operands = {n: [x.aval.shape for x in e.invars] for n, e in calls.items()}
    handed = {n: [(x.aval.shape, x.aval.dtype) for x in e.outvars]
              for n, e in calls.items()}
    folded = lambda n, width: (2 * n, length, width)
    own = lambda n, width: (2, length, n * width)
    row = (2 * heads, 1, length)
    first_three = [folded(heads, d), folded(kv, d), (2 * kv, d_v, length)]
    assert operands[name("flash_fwd")] == first_three
    assert operands[name("flash_bwd")][:4] == [
        folded(heads, d), folded(kv, d), folded(kv, d_v), (2 * kv, d, length)]
    sums = ((2 * heads, d, length), jnp.float32)
    if results:
        assert operands[name("flash_bwd")][4:] == [
            own(heads, d_v), row, own(heads, d_v)]
        assert handed[name("flash_fwd")] == [(own(heads, d_v), dtype),
                                             (row, jnp.float32)]
        assert handed[name("flash_bwd")] == [
            sums, (own(kv, d), dtype), (own(kv, d_v), dtype)]
        # nothing outside the kernels reduces: ``delta`` is the kernel's
        assert not _reductions(jaxpr)
    else:
        assert operands[name("flash_bwd")][4:] == [
            folded(heads, d_v), row, row]
        assert handed[name("flash_fwd")] == [((2 * heads, d_v, length), dtype),
                                             (row, jnp.float32)]
        assert handed[name("flash_bwd")] == [
            sums, (folded(kv, d), dtype), (folded(kv, d_v), dtype)]


def test_the_boundary_taken_is_in_the_ring(monkeypatch):
    """One ``attention/boundary`` record a traced call of the kernel's
    path: heads, widths, heads a lane tile, whether the kernels address
    the model's arrays and whether their results alone cross in them;
    GPT-2 XL's 25 heads are admitted, a group is not."""
    from ray_tpu._private import steptrace

    monkeypatch.setattr(attention, "flash_attention", functools.partial(
        flash_attention, impl="pallas"))
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        jax.clear_caches()
        for heads, kv, seq, d in ((25, 25, 1024, 64), (8, 2, 1024, 64),
                                  (8, 1, 4096, 128), (8, 8, 4096, 64)):
            q = jax.ShapeDtypeStruct((4, seq, heads, d), jnp.bfloat16)
            k = jax.ShapeDtypeStruct((4, seq, kv, d), jnp.bfloat16)
            jax.eval_shape(lambda *x: causal_self_attention(*x, "flash"),
                           q, k, k)
        records = [r["values"] for r in steptrace.snapshot()
                   if r["kind"] == "counters"
                   and r["name"] == "attention/boundary"]
    finally:
        steptrace.set_enabled(False)
        jax.clear_caches()
    shared = {"tokens": 1024, "d_qk": 64, "d_v": 64, "window": 0,
              "model_results": 0}
    assert records == [
        shared | {"heads": 25, "kv_heads": 25, "heads_a_lane_tile": 2,
                  "model_arrays": 1},
        shared | {"heads": 8, "kv_heads": 2, "heads_a_lane_tile": 0,
                  "model_arrays": 0},
        # several blocks of keys a head: the results alone, at one width of
        # whole lane tiles, whatever the group (PR 55); not at 64
        shared | {"tokens": 4096, "heads": 8, "kv_heads": 1, "d_qk": 128,
                  "d_v": 128, "heads_a_lane_tile": 0, "model_arrays": 0,
                  "model_results": 1},
        shared | {"tokens": 4096, "heads": 8, "kv_heads": 8,
                  "heads_a_lane_tile": 0, "model_arrays": 0}]


# sha256 (first 16 digits) of the jaxpr's text, forward and gradient, of a
# call without a window and with as many key-value heads, the kernels' names
# included. (q, k, v shapes, causal.) The forward's are those of the parent
# of PR 44 (commit b403d28). The gradient's are PR 50's, whose backward sums
# dQ^T itself over several blocks of keys; at one block a head
# (``gpt2_1024_64``, ``batch_and_heads_1024``) they moved by the call's first
# output alone, [B x H, d, T] where it was [1, B x H, d, T] and a slice: the
# kernel's body there is the parent's equation for equation.
_PARENT = {
    "gpt2_1024_64": (((192, 1024, 64),) * 3, True,
                     "5f3323b1f1ffc1ef", "17b0a9d77b23fd90"),
    "several_blocks_4096_64": (((48, 4096, 64),) * 3, True,
                               "0e0c8415dffe7b78", "4e729f280b87332e"),
    "latent_8192_192_128": (((64, 8192, 192), (64, 8192, 192),
                             (64, 8192, 128)), True,
                            "1c74b3b506b9de82", "01fd634a60681405"),
    "cross_4096_8192_looped": (((8, 4096, 128), (8, 8192, 128),
                                (8, 8192, 128)), True,
                               "3492ea75658517fb", "1e74eceabd605ac9"),
    "no_mask_4096": (((8, 4096, 128),) * 3, False,
                     "47ba714f8f7f46f9", "c6c96bfda3a1c7fb"),
    "batch_and_heads_1024": (((2, 12, 1024, 64),) * 3, True,
                             "6118297c0a2f298f", "bbf997deeeae1289"),
}


@pytest.mark.parametrize("backward", [False, True], ids=["fwd", "grad"])
@pytest.mark.parametrize("case", _PARENT)
def test_a_call_without_either_lowers_to_the_held_jaxpr(case, backward):
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

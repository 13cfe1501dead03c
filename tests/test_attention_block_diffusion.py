"""The block-diffusion mask in the attention ops (PR 65): two streams of one
sequence, a noisy and a clean copy, in blocks of D tokens. The dense mask
against the four sentences that define it, written out by hand; the Pallas
kernels in interpret mode and the ``jnp`` path against it, forward and
backward, at D 4 and other Ds, with tiles that hold many blocks' edges; the
grid's blocks by kind; what does not leak between the streams; the kernels'
names and the records; the norm-and-rotation prologue over positions that
repeat; what recomputation keeps."""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.ops import attention, flash_kernels, rotary
from ray_tpu.ops.attention import (attention_reference, causal_self_attention,
                                   flash_attention,
                                   normed_rotary_self_attention, seen_by_block)
from ray_tpu.ops.remat import remat_policy
from tests.conftest import kernel_calls


def _by_hand(length: int, block: int) -> np.ndarray:
    """The [2 length, 2 length] mask from ISSUE 65's sentences, a pair at a
    time."""
    seen = np.zeros((2 * length, 2 * length), bool)
    for i in range(length):
        for j in range(length):
            # a query of the noisy stream at i
            seen[i, j] = j // block == i // block            # noisy keys
            seen[i, length + j] = j // block < i // block    # clean keys
            # a query of the clean stream at i: no noisy key
            seen[length + i, length + j] = j // block <= i // block
    return seen


@pytest.mark.parametrize("length,block", [(16, 4), (24, 3), (8, 8), (12, 1)])
def test_the_dense_mask_is_the_four_sentences(length, block):
    at = np.arange(2 * length)
    got = np.asarray(seen_by_block(at[:, None], at[None, :], block, length))
    want = _by_hand(length, block)
    np.testing.assert_array_equal(got, want)
    # L D + L (L - D) / 2 + L (L + D) / 2 = L^2 + L D pairs a head
    assert want.sum() == length * length + length * block


def _operands(heads, kv, length, d, dtype=jnp.float32, batch=2, seed=3):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (batch, length, heads, d), dtype),
            jax.random.normal(ks[1], (batch, length, kv, d), dtype),
            jax.random.normal(ks[2], (batch, length, kv, d), dtype),
            jax.random.normal(ks[3], (batch, length, heads, d), jnp.float32))


def _out_and_grads(fn, w, *args):
    out, vjp = jax.vjp(fn, *args)
    return (out, *vjp(w))


_bhsd = lambda t: t.transpose(0, 2, 1, 3)


def _dense(block, q, k, v):
    """Softmax attention under the hand-written mask, a model's layout."""
    mask = jnp.asarray(_by_hand(q.shape[1] // 2, block))
    group = q.shape[2] // k.shape[2]
    k, v = (jnp.repeat(t, group, axis=2) for t in (k, v))
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * q.shape[-1] ** -0.5
    p = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


# (query heads, key-value heads, a stream's length, width, block, resident
# queries and keys, (block_q, block_k)): residents of one, two and four grid
# blocks a stream; tiles of 16 hold four blocks of 4, two of 8, and with
# (8, 16) / (32, 16) the two kernels' tiles differ from each other
_CASES = {
    "d4_two_blocks_a_stream": (4, 2, 64, 16, 4, 32, (16, 16)),
    "d4_one_block_a_stream": (4, 4, 64, 16, 4, 64, (16, 16)),
    "d4_four_blocks_a_stream": (2, 1, 64, 16, 4, 16, (16, 16)),
    "d4_queries_in_half_tiles": (4, 2, 64, 16, 4, 32, (8, 16)),
    "d4_keys_in_half_tiles": (4, 2, 64, 16, 4, 32, (16, 8)),
    "d4_a_tile_a_resident": (4, 2, 64, 16, 4, 32, (32, 16)),
    "d8": (4, 2, 64, 16, 8, 32, (16, 16)),
    "d2": (2, 2, 64, 16, 2, 32, (16, 16)),
    "d16_a_block_a_tile": (2, 1, 64, 16, 16, 32, (16, 16)),
}


@pytest.mark.parametrize("case", _CASES)
def test_the_kernels_match_the_dense_mask(monkeypatch, case):
    """``causal_self_attention(..., "flash", blocks=D)`` in interpret mode
    against the hand-written mask: output and three gradients."""
    heads, kv, length, d, block, resident, tiles = _CASES[case]
    monkeypatch.setattr(flash_kernels, "_MAX_RESIDENT", resident)
    monkeypatch.setattr(attention, "flash_attention", functools.partial(
        flash_attention, impl="pallas_interpret", block_q=tiles[0],
        block_k=tiles[1]))
    jax.clear_caches()
    q, k, v, w = _operands(heads, kv, 2 * length, d)
    flash = lambda *x: causal_self_attention(*x, "flash", None, block)
    try:
        got = jax.jit(functools.partial(_out_and_grads, flash, w))(q, k, v)
    finally:
        jax.clear_caches()
    want = _out_and_grads(functools.partial(_dense, block), w, q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("block", [4, 32])
def test_the_jnp_path_matches_the_dense_mask(block):
    """``"xla"`` under ``blocks`` is the reference's dense mask; so is
    ``flash_attention(impl="reference")``; the scan has no such mask."""
    q, k, v, w = _operands(4, 2, 128, 8)
    want = _out_and_grads(functools.partial(_dense, block), w, q, k, v)
    got = _out_and_grads(
        lambda *x: causal_self_attention(*x, "xla", None, block), w, q, k, v)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=2e-5, rtol=2e-5)
    again = _bhsd(flash_attention(*map(_bhsd, (q, k, v)), causal=True,
                                  impl="reference", blocks=block))
    np.testing.assert_allclose(again, want[0], atol=2e-5, rtol=2e-5)
    with pytest.raises(AssertionError):
        flash_attention(*map(_bhsd, (q, k, v)), causal=True, impl="scan",
                        blocks=block)


def test_the_results_cross_in_the_models_arrays(monkeypatch):
    """Past one block of keys a head at a width of whole lane tiles the
    bd kernels take the ``model_results`` boundary as the causal ones do
    (PR 55): O, dK and dV are the model's [B, T, H x d], dQ the float32 [B
    x H, d, T] sum; the names carry the mask's kind and D; bfloat16 too."""
    heads, kv, length, d, block = 8, 1, 96, 128, 4
    monkeypatch.setattr(flash_kernels, "_MAX_RESIDENT", 32)
    assert attention.results_in_model_arrays(2 * length, d, d)
    monkeypatch.setattr(attention, "flash_attention", functools.partial(
        flash_attention, impl="pallas_interpret", block_q=16, block_k=16))
    jax.clear_caches()
    f32 = lambda x: x.astype(jnp.float32)
    flash = lambda *x: f32(causal_self_attention(*x, "flash", None, block))
    try:
        for dtype, tol in ((jnp.float32, 1e-4), (jnp.bfloat16, 3e-2)):
            q, k, v, w = _operands(heads, kv, 2 * length, d, dtype)
            got = jax.jit(functools.partial(_out_and_grads, flash, w))(q, k, v)
            want = _out_and_grads(functools.partial(_dense, block), w,
                                  f32(q), f32(k), f32(v))
            for a, b in zip(got, want):
                assert float(jnp.abs(f32(a) - b).max()
                             / jnp.abs(b).max()) <= tol
        jaxpr = jax.make_jaxpr(
            lambda *x: _out_and_grads(flash, w, *x))(q, k, v)
    finally:
        jax.clear_caches()
    calls = {}

    def walk(j):
        for eqn in j.eqns:
            if eqn.primitive.name == "pallas_call":
                calls[eqn.params["name"]] = eqn
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jaxpr.jaxpr)
    assert set(calls) == {"flash_fwd_bd4", "flash_bwd_bd4"}
    own = lambda n: (2, 2 * length, n * d)
    assert [x.aval.shape for x in calls["flash_fwd_bd4"].outvars] == [
        own(heads), (2 * heads, 1, 2 * length)]
    assert [(x.aval.shape, x.aval.dtype)
            for x in calls["flash_bwd_bd4"].outvars] == [
        ((2 * heads, d, 2 * length), jnp.float32),
        (own(kv), jnp.bfloat16), (own(kv), jnp.bfloat16)]
    # nothing of [2L, 2L] a head anywhere in the program
    for eqn in jaxpr.jaxpr.eqns:
        for x in (*eqn.invars, *eqn.outvars):
            dims = getattr(x.aval, "shape", ())
            assert dims[-2:] != (2 * length, 2 * length), eqn


def test_nothing_leaks_between_the_streams(monkeypatch):
    """The noisy stream's output in block b does not move when clean keys
    and values of blocks >= b or noisy ones of other blocks change; the
    clean stream's output does not move with the noisy stream at all: the
    kernels (interpreted) and the ``jnp`` path alike."""
    heads, kv, length, d, block = 2, 1, 32, 16, 4
    monkeypatch.setattr(flash_kernels, "_MAX_RESIDENT", 16)
    monkeypatch.setattr(attention, "flash_attention", functools.partial(
        flash_attention, impl="pallas_interpret", block_q=8, block_k=8))
    jax.clear_caches()
    q, k, v, _ = _operands(heads, kv, 2 * length, d, batch=1)
    other = _operands(heads, kv, 2 * length, d, batch=1, seed=11)
    b = 3                                   # the block that is watched
    mine = slice(b * block, (b + 1) * block)
    at = np.arange(2 * length)
    noisy_elsewhere = (at < length) & (at // block != b)
    clean_from_b = (at >= length) & ((at - length) // block >= b)
    swap = lambda x, y, rows: jnp.where(
        jnp.asarray(rows)[None, :, None, None], y, x)
    try:
        for path in ("flash", "xla"):
            run = jax.jit(lambda *x, path=path: causal_self_attention(
                *x, path, None, block))
            base = run(q, k, v)
            rows = noisy_elsewhere | clean_from_b
            moved = run(swap(q, other[0], rows), swap(k, other[1], rows),
                        swap(v, other[2], rows))
            np.testing.assert_array_equal(base[:, mine], moved[:, mine])
            assert not np.allclose(base[:, length:], moved[:, length:])
            # the whole noisy stream swapped: the clean stream stands
            moved = run(*(swap(x, y, at < length)
                          for x, y in zip((q, k, v), other)))
            np.testing.assert_array_equal(base[:, length:],
                                          moved[:, length:])
    finally:
        jax.clear_caches()


def test_the_grids_blocks_by_kind():
    """Of a stream's n grid blocks squared four times over: n (n - 1)
    whole, n each of the three kinds an edge crosses, the rest dead; the
    cell's call (8,192 positions: two streams of two residents of 2,048)
    walks 8 of 16 blocks a head."""
    for n in (1, 2, 4, 8):
        counts = flash_kernels.by_block_counts(n)
        assert sum(counts.values()) == 4 * n * n
        assert counts["whole"] == n * (n - 1)
        assert counts["own"] == counts["strict"] == counts["inclusive"] == n
        place = np.zeros((2 * n, 2 * n), object)
        for qi in range(2 * n):
            for ki in range(2 * n):
                kinds = [k for k, here in flash_kernels._by_block_place(
                    jnp.int32(qi), jnp.int32(ki), n).items() if bool(here)]
                assert len(kinds) == 1, (qi, ki, kinds)
                place[qi, ki] = kinds[0]
        for kind, count in counts.items():
            assert (place == kind).sum() == count, (n, kind)
    for backward in (False, True):
        assert flash_kernels.by_block_kinds(8192, 4, backward=backward) == {
            "whole": 2, "own": 2, "strict": 2, "inclusive": 2, "dead": 8}
    assert flash_kernels.by_block_fits(8192, 4)
    assert not flash_kernels.by_block_fits(8192, 3)      # no whole blocks
    assert not flash_kernels.by_block_fits(8190, 4)
    assert not flash_kernels.by_block_fits(8192, 4, 126, None)


def test_the_call_is_named_and_recorded(monkeypatch):
    """One ``attention/boundary`` record a traced call says the mask's
    block length, whether the kernels ran and the forward grid's live and
    skipped blocks; ``attn/grid_blocks`` the kinds; "auto" leaves a length
    the kernels refuse to the dense mask."""
    from ray_tpu._private import steptrace

    monkeypatch.setattr(attention, "flash_attention", functools.partial(
        flash_attention, impl="pallas"))
    monkeypatch.setattr(attention, "auto_attention", lambda q, v: "flash")
    steptrace.set_enabled(True)
    steptrace.reset()
    try:
        jax.clear_caches()
        q = jax.ShapeDtypeStruct((2, 8192, 32, 128), jnp.bfloat16)
        k = jax.ShapeDtypeStruct((2, 8192, 4, 128), jnp.bfloat16)
        jaxpr = jax.make_jaxpr(jax.grad(lambda *x: causal_self_attention(
            *x, "auto", None, 4).astype(jnp.float32).sum()))(q, k, k)
        # 4,100 positions a stream are no whole number of tiles
        odd = jax.ShapeDtypeStruct((1, 8200, 4, 128), jnp.bfloat16)
        jax.eval_shape(lambda *x: causal_self_attention(
            *x, "auto", None, 4), odd, odd, odd)
        records = [r for r in steptrace.snapshot() if r["kind"] == "counters"]
    finally:
        steptrace.set_enabled(False)
        jax.clear_caches()
    assert kernel_calls(jaxpr) == {"flash_fwd_bd4": 1, "flash_bwd_bd4": 1}
    boundary = [r["values"] for r in records
                if r["name"] == "attention/boundary"]
    shared = {"d_qk": 128, "d_v": 128, "window": 0, "heads_a_lane_tile": 0,
              "model_arrays": 0, "blocks": 4}
    assert boundary == [
        shared | {"tokens": 8192, "heads": 32, "kv_heads": 4,
                  "model_results": 1, "kernel": 1, "live_blocks": 64 * 8,
                  "skipped_blocks": 64 * 8},
        shared | {"tokens": 8200, "heads": 4, "kv_heads": 4,
                  "model_results": 0, "kernel": 0, "live_blocks": 0,
                  "skipped_blocks": 0}]
    grid = [r["values"] for r in records if r["name"] == "attn/grid_blocks"]
    # the forward is traced for the primal and for the rule
    assert {g["backward"] for g in grid} == {0, 1}
    for g in grid:
        assert g == {"whole": 2, "own": 2, "strict": 2, "inclusive": 2,
                     "dead": 8, "queries": 8192, "keys": 8192,
                     "backward": g["backward"], "window": 0, "blocks": 4,
                     "heads": 64, "kv_heads": 8, "dq_partials": 0,
                     "steps": 16, "dead_steps": 8}


def test_the_prologue_serves_positions_that_repeat(monkeypatch):
    """``normed_rotary_self_attention(..., blocks=D)`` with PR 63's kernel
    pair (interpreted) under a table that holds a stream's positions twice,
    against the ``jnp`` form over the dense mask: output and the gradients
    of q, k, v and both scales; recomputation keeps the kernel's output
    and log-sum-exp under their names."""
    from ray_tpu.models.llama import rope_table

    heads, kv, length, d, block = 2, 1, 32, 128, 4
    monkeypatch.setattr(flash_kernels, "_MAX_RESIDENT", 16)
    jax.clear_caches()
    q, k, v, w = _operands(heads, kv, 2 * length, d)
    scales = (1.0 + 0.1 * jax.random.normal(jax.random.PRNGKey(5), (2, d)))
    positions = jnp.tile(jnp.arange(length), 2)[None, :]
    cos, sin = rope_table(d, positions, {"rope_type": "default",
                                         "rope_theta": 10000.0})
    assert rotary.fits(q, cos)

    def call(impl, attention_path, q, k, v, q_scale, k_scale):
        return normed_rotary_self_attention(
            q, k, v, q_scale, k_scale, cos, sin, eps=1e-6,
            attention=attention_path, impl=impl, block_q=8, block_k=8,
            blocks=block)

    try:
        got = jax.jit(functools.partial(
            _out_and_grads, functools.partial(
                call, "pallas_interpret", "flash"), w))(q, k, v, *scales)
        want = _out_and_grads(functools.partial(call, "jnp", "xla"), w,
                              q, k, v, *scales)
        kept = jax.checkpoint(
            lambda *x: call("pallas_interpret", "flash", *x).sum(),
            policy=remat_policy())
        jaxpr = jax.make_jaxpr(jax.grad(kept))(q, k, v, *scales)
    finally:
        jax.clear_caches()
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, atol=5e-5, rtol=5e-5)
    # one forward, one backward: the forward is not run again
    calls = kernel_calls(jaxpr)
    assert calls["flash_fwd_bd4"] == calls["flash_bwd_bd4"] == 1
